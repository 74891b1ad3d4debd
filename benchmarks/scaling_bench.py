"""Paper Fig. 5: multicore scaling of F+Nomad LDA.

Runs the distributed sweep on 1/2/4/8 faked host devices (subprocesses, so
the main process keeps one device) and reports tokens/s plus the LL
trajectory — convergence must be preserved while throughput scales.

On this 1-core container the *wall-clock* speedup is bounded by real
parallelism (≈1); what the benchmark proves is (a) identical convergence
across ring widths — the paper's asynchronous-correctness claim — and
(b) per-sweep work split into W cells with the imbalance reported by the
layout (the 'last reducer' exposure the paper attacks with asynchrony and
we attack with LPT balancing)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.util import cpu_child_env, row

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(devices=(1, 2, 4, 8)) -> list[str]:
    out = []
    lls = {}
    for n in devices:
        env = cpu_child_env(REPO)
        t0 = time.time()
        res = subprocess.run(
            [sys.executable, "-m", "repro.launch.lda_dist_check",
             str(n), "stoken", "1"],
            capture_output=True, text=True, env=env, timeout=900)
        wall = time.time() - t0
        if res.returncode != 0:
            out.append(row(f"fig5/nomad_{n}dev", -1.0,
                           "ERROR " + res.stderr[-200:]))
            continue
        rep = json.loads(res.stdout.strip().splitlines()[-1])
        n_swept = rep["n_tokens"] * (len(rep["ll"]) - 1)
        lls[n] = rep["ll"][-1]
        out.append(row(
            f"fig5/nomad_{n}dev", wall * 1e6 / max(n_swept, 1),
            f"platform=cpu;final_ll={rep['ll'][-1]:.0f};imbalance="
            f"{rep['round_imbalance']:.2f};exact="
            f"{rep['n_td_mismatch'] + rep['n_wt_mismatch'] == 0}"))
    if len(lls) > 1:
        vals = list(lls.values())
        spread = (max(vals) - min(vals)) / abs(min(vals))
        out.append(row("fig5/convergence_spread_pct", spread * 100,
                       "ring width does not change convergence"
                       if spread < 0.05 else "WARN"))
    return out
