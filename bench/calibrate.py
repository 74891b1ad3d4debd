#!/usr/bin/env python3
"""Readings that the limits of a training cell's check are set from.

    python3 bench/calibrate.py --workload nytimes.train \\
        --seeds 11,12,13 --sweeps 1 [--faults half,unchanged,token,ring \\
        --fault-seeds 3]
    python3 bench/calibrate.py --workload nytimes.train --control-runs 21,22

The first form builds the cell once (its corpus and layout are fixed by
the configuration) and, for each seed, warms the trainer up and drives it
through ``--sweeps`` sweeps from that seed's chain, as a run's window
does, then reads on the same sampled draws:

* ``program`` — the draw gap of each sweep (``bench/reference.py``);
* ``control`` — the gap of the topic the same draw picks when the
  reference's arithmetic runs in bfloat16, one precision below the
  configuration's float32 (computed on the chip);

and, with ``--faults``, the readings of the timed path broken underneath:

* ``unchanged`` — a sweep that returns its state as it got it;
* ``half`` — every other token left out of the sweep (its ``tok_valid``
  cleared, so the kernel passes it by);
* ``token`` — one token's new topic altered after the sweep
  (``count_mismatch``);
* ``ring`` — the ring's exchange left out (``ppermute`` replaced by the
  identity; needs more than one chip, and compiles the sweep anew).

One JSON line per seed on standard output, and a summary line last.

The second form makes whole runs of the harness, one per seed, with the
control in the program's place (:func:`control_sweep`), and prints each
run's ``correct`` and the numbers it compared.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402

import reference as ref  # noqa: E402
import run as harness  # noqa: E402

train = harness.load_module(BENCH / "traffic" / "train.py")


def sweeps(trainer, arrays, seed, n):
    zs = [arrays["z"]]
    for i in range(n):
        arrays = train.sweep_once(trainer, arrays, seed + i)
        zs.append(arrays["z"])
    return arrays, zs


def no_exchange(fn):
    """``fn()`` with the ring's ``ppermute`` replaced by the identity
    while the sweep is traced (its first call)."""
    import repro.core.nomad as nomad
    keep = nomad._ring_shift_down
    nomad._ring_shift_down = lambda x, axes, sizes: x
    try:
        return fn()
    finally:
        nomad._ring_shift_down = keep


def control_sweep(orig, sample_seed):
    """``NomadLDA.sweep`` with the control in the program's place.  After
    the program's sweep ``orig``, every token the check of a run with seed
    ``sample_seed`` will read takes the topic that the reference's draw
    picks in bfloat16, at the same counts and uniform; the count tables
    are recounted to match, so only the precision of the draws differs."""
    import jax
    import jax.numpy as jnp
    draws = train.sample_draws(sample_seed)
    schedules = {}

    def sweep(self, arrays, seed):
        out = orig(self, arrays, seed)
        lay = self.layout
        if id(lay) not in schedules:
            schedules[id(lay)] = ref.schedule(train.layout_arrays(lay))
        sch = schedules[id(lay)]
        z = np.asarray(out["z"]).copy()
        flat = z.reshape(-1)
        zb = np.asarray(arrays["z"]).reshape(-1)[sch.pos].astype(np.int64)
        idx = draws(sch.pos.size)
        state = ref.visit_state(sch, zb, flat[sch.pos].astype(np.int64),
                                idx, lay.T)
        flat[sch.pos[idx]] = ref.pick(
            state, ref.uniforms(sch, idx, seed), alpha=self.alpha,
            beta=self.beta, beta_bar=self.beta_bar, dtype=jnp.bfloat16)
        keys = ("n_td", "n_wt", "n_t")
        tables = ref.recount(sch, z, [out[k].shape for k in keys])
        put = lambda a, like: jax.device_put(a.astype(np.int32),
                                             like.sharding)
        return dict(out, z=put(z, out["z"]),
                    **{k: put(t, out[k]) for k, t in zip(keys, tables)})
    return sweep


def control_runs(args, root: Path) -> int:
    """Whole harness runs with :func:`control_sweep` in the program's
    place; one JSON line per seed."""
    from repro.core.nomad import NomadLDA
    orig = NomadLDA.sweep
    for seed in (int(s) for s in args.control_runs.split(",")):
        NomadLDA.sweep = control_sweep(orig, seed)
        try:
            out = harness.execute(
                harness.parse(["--workload", args.workload, "--seed",
                               str(seed), "--seconds", "1", "--trace",
                               "0"]),
                root=root, require_tpu=not args.cpu,
                interpret=None if args.cpu else False, cache=not args.cpu)
        finally:
            NomadLDA.sweep = orig
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


def readings_for(cfg, lay, corpus, arrays, zs, seeds, sample_seed):
    final = tuple(np.asarray(arrays[k]) for k in ("n_td", "n_wt", "n_t"))
    zs = [np.asarray(z) for z in zs]
    rd, gaps = train.check(cfg, corpus, lay, zs, seeds, final,
                           sample_seed=sample_seed)
    return rd, gaps, zs, final


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sweeps", type=int, default=2)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=None,
                    help="plant the faults on the first N seeds only")
    ap.add_argument("--control-runs", default="",
                    help="seeds of whole runs with the control in the "
                         "program's place")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU with interpreted kernels (tests)")
    ap.add_argument("--root", default=str(BENCH))
    args = ap.parse_args(argv)
    root = Path(args.root)
    if args.control_runs:
        return control_runs(args, root)
    faults = [f for f in args.faults.split(",") if f]
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax
    import jax.numpy as jnp
    wl = harness.load_json(root, "workloads", args.workload)
    cfg = harness.load_json(root, "configs", wl["config"])
    chips = int(wl["chips"])
    if not args.cpu:
        jax.config.update("jax_compilation_cache_dir",
                          str(harness.CACHE_DIR))
    devices = harness.devices_for(chips, not args.cpu)
    ctx = harness.Context(config=cfg, workload=wl, chips=chips,
                          devices=devices, interpret=None if args.cpu
                          else False, spans={}, trace=False)
    t0 = time.perf_counter()
    corpus, lay, trainer = train.build(ctx)
    sch = ref.schedule(train.layout_arrays(lay))
    beta = float(cfg["beta"])
    kw = dict(alpha=float(cfg["alpha"]), beta=beta,
              beta_bar=beta * int(cfg["vocab_size"]))
    print(json.dumps({"built_s": time.perf_counter() - t0,
                      "tokens": int(corpus.num_tokens)}), flush=True)
    ring_trainer = (train.make_trainer(ctx, lay) if "ring" in faults
                    else None)

    rows = []
    every = faults
    for n, seed in enumerate(seeds):
        faults = every if args.fault_seeds is None or n < args.fault_seeds \
            else []
        t1 = time.perf_counter()
        init_seed, base = train.chain_seeds(seed)
        start = trainer.init_arrays(seed=init_seed)
        train.warm_up(trainer, start, base)
        arrays, zs = sweeps(trainer, start, base, args.sweeps)
        wseeds = list(range(base, base + args.sweeps))
        rd, gaps, zs_h, final = readings_for(cfg, lay, corpus, arrays, zs,
                                             wseeds, seed)
        row = {"seed": seed, "program": gaps, "readings": rd}
        drawn = train.sampled_draws(cfg, sch, zs_h, wseeds, sample_seed=seed)
        row["control"] = [
            float(ref.draw_gap(st, u, ref.pick(st, u, dtype=jnp.bfloat16,
                                                **kw), **kw).max())
            for st, u, _ in drawn]
        if "unchanged" in faults:
            same = [zs_h[0]] * (args.sweeps + 1)
            row["unchanged"] = max(train.gap_of(cfg, *d) for d in
                                   train.sampled_draws(cfg, sch, same, wseeds,
                                                       sample_seed=seed))
        if "token" in faults:
            z_bad = zs_h[-1].copy().reshape(-1)
            z_bad[sch.pos[0]] = (z_bad[sch.pos[0]] + 1) % int(
                cfg["num_topics"])
            row["token"] = ref.count_mismatch(sch, z_bad.reshape(
                zs_h[-1].shape), *final)
        if "half" in faults:
            valid = np.asarray(lay.tok_valid).copy().reshape(-1)
            valid[sch.pos[::2]] = False
            half = dict(start, tok_valid=jax.device_put(
                valid.reshape(lay.tok_valid.shape),
                start["tok_valid"].sharding))
            a_h, zs_f = sweeps(trainer, half, base, args.sweeps)
            rd_h, gaps_h, _, _ = readings_for(cfg, lay, corpus, a_h, zs_f,
                                              wseeds, seed)
            row["half"] = {"draw_gap": max(gaps_h), **{
                k: v for k, v in rd_h.items() if k != "draw_gap"}}
        if "ring" in faults:
            a_r, zs_r = no_exchange(lambda: sweeps(
                ring_trainer, start, base, args.sweeps))
            rd_r, gaps_r, _, _ = readings_for(cfg, lay, corpus, a_r, zs_r,
                                              wseeds, seed)
            row["ring"] = {"draw_gap": max(gaps_r), **{
                k: v for k, v in rd_r.items() if k != "draw_gap"}}
        row["seconds"] = time.perf_counter() - t1
        rows.append(row)
        print(json.dumps(row), flush=True)

    summary = {"lower_draw_gap": max(max(r["program"]) for r in rows),
               "control_min": min(max(r["control"]) for r in rows),
               "seeds": len(rows)}
    for f in ("unchanged", "half", "ring"):
        vals = [r[f] if f == "unchanged" else r[f]["draw_gap"]
                for r in rows if f in r]
        if vals:
            summary[f"{f}_min"] = min(vals)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
