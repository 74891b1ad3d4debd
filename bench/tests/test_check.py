"""The correctness check: sound runs pass it, and its control and every
fault a training cell can have fail it.

Each fault test drives a whole run through the harness — everything but
its look for a chip — with the timed path broken underneath, and sees
``correct`` come out false.  Sizes are the CPU's, kernels interpreted.
"""
import json
import os
import time
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import calibrate
import run

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def execute(workload="tiny.train", seed=5, seconds=0.3):
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    return run.execute(args, root=DATA, require_tpu=False, interpret=None,
                       cache=False)


def test_sound_run_is_correct():
    out = execute()
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] >= 1
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def test_control_fails_and_program_passes(capsys):
    """The bfloat16 control reads above the limit on every seed; the
    float32 program reads below it."""
    calibrate.main(["--workload", "tiny.train", "--root", str(DATA),
                    "--seeds", "1,2,3", "--sweeps", "2", "--cpu"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limit = json.loads((DATA / "workloads" / "tiny.train.json")
                       .read_text())["limits"]["draw_gap"]
    assert summary["lower_draw_gap"] <= limit < summary["control_min"]


def test_control_in_the_programs_place_fails(monkeypatch):
    """A whole run with the bfloat16 control in the program's place: the
    tables still match a recount, and the draws fail ``draw_gap``."""
    from repro.core.nomad import NomadLDA
    monkeypatch.setattr(NomadLDA, "sweep",
                        calibrate.control_sweep(NomadLDA.sweep, 5))
    out = execute(seed=5)
    assert out["correct"] is False
    checks = out["checks"]
    assert checks["draw_gap"]["value"] > checks["draw_gap"]["limit"]
    assert checks["count_mismatch"]["value"] == 0


def test_control_runs_print_their_checks(capsys):
    calibrate.main(["--workload", "tiny.train", "--root", str(DATA),
                    "--control-runs", "6", "--cpu"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["seed"] == 6 and row["correct"] is False
    assert row["checks"]["draw_gap"]["value"] > 0.001


@pytest.fixture
def broken(monkeypatch):
    """Replace ``NomadLDA.sweep`` by ``wrap(original)``."""
    from repro.core.nomad import NomadLDA
    orig = NomadLDA.sweep

    def install(wrap):
        monkeypatch.setattr(NomadLDA, "sweep", wrap(orig))
    return install


def test_state_left_unchanged_fails(broken):
    def wrap(orig):
        def sweep(self, arrays, seed):
            time.sleep(0.05)                 # a sweep's time, no work
            return arrays
        return sweep
    broken(wrap)
    out = execute()
    assert not out["correct"]
    assert out["checks"]["draw_gap"]["value"] > 0.1


def test_half_the_batch_left_out_fails(broken):
    def wrap(orig):
        def sweep(self, arrays, seed):
            valid = np.asarray(arrays["tok_valid"]).copy()
            flat = valid.reshape(-1)
            flat[np.flatnonzero(flat)[::2]] = False
            import jax
            half = dict(arrays, tok_valid=jax.device_put(
                valid, arrays["tok_valid"].sharding))
            return dict(orig(self, half, seed),
                        tok_valid=arrays["tok_valid"])
        return sweep
    broken(wrap)
    out = execute()
    assert not out["correct"]
    assert out["checks"]["draw_gap"]["value"] > 0.1


def test_token_altered_fails(broken):
    def wrap(orig):
        def sweep(self, arrays, seed):
            out = orig(self, arrays, seed)
            z = np.asarray(out["z"]).copy()
            i = np.flatnonzero(np.asarray(arrays["tok_valid"]))[0]
            z.reshape(-1)[i] = (z.reshape(-1)[i] + 1) % self.layout.T
            import jax
            return dict(out, z=jax.device_put(z, out["z"].sharding))
        return sweep
    broken(wrap)
    out = execute()
    assert not out["correct"]
    assert out["checks"]["count_mismatch"]["value"] > 0


def flat_count_mismatch(sch, z, tables):
    """The whole-table recount that ``count_mismatch`` replaced."""
    import reference as ref
    tables = [np.asarray(t) for t in tables]
    return sum(int(np.count_nonzero(c[:t.size] != t.reshape(-1)))
               + int(c.size > t.size) for c, t in
               zip(ref._recounts(sch, z, [t.shape for t in tables]), tables))


@pytest.mark.parametrize("fault", ["none", "entry", "row"])
def test_blockwise_count_equals_flat(fault):
    """The recount one slab at a time counts what a recount of the whole
    table counts: with one entry altered, and with one token's row past
    the end of the table."""
    import reference as ref
    rng = np.random.default_rng(3)
    W, B, J, Dl, T, n = 2, 6, 5, 7, 8, 400
    w = rng.integers(0, W, n)
    sch = ref.Schedule(
        pos=np.arange(n), w=w, r=w, s=np.arange(n), block=rng.integers(0, B, n),
        doc=None, word=None, d_loc=rng.integers(0, Dl, n),
        j_loc=rng.integers(0, J, n), uid=None, W=W, S=n)
    z = rng.integers(0, T, n)
    shapes = [(W, Dl, T), (B, J, T), (T,)]
    tables = [t.astype(np.int32) for t in ref.recount(sch, z, shapes)]
    if fault == "entry":
        tables[1][3, 2, 5] += 1
    elif fault == "row":
        sch.j_loc[17] = J * B          # past the last block's page
    got = ref.count_mismatch(sch, z, *tables)
    assert got == flat_count_mismatch(sch, z, tables)
    assert got == {"none": 0, "entry": 1, "row": 2}[fault]


RING = """
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import run
import repro.core.nomad as nomad
if {broken}:
    nomad._ring_shift_down = lambda x, axes, sizes: x
args = run.parse(["--workload", "tiny4.train", "--seed", "8",
                  "--seconds", "0.3", "--trace", "0"])
out = run.execute(args, root=run.Path({data!r}), require_tpu=False,
                  interpret=None, cache=False)
print(json.dumps({{"correct": out["correct"], "checks": out["checks"]}}))
"""


@pytest.mark.parametrize("broken_ring", [False, True])
def test_exchange_between_chips(broken_ring):
    """On four virtual CPU devices: the ring passes, and fails with its
    ``ppermute`` left out."""
    code = RING.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                       data=str(DATA), broken=broken_ring)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not broken_ring), out["checks"]
