"""The harness finds cells, configurations and metrics by name, and
refuses to run without the chips a cell asks for."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import run

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def test_new_cell_and_metric_found_by_name(tmp_path):
    """A workload JSON and a metric reader dropped into their directories
    run with no other file edited."""
    (tmp_path / "workloads").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "metrics").mkdir()
    shutil.copy(DATA / "configs" / "tiny.json", tmp_path / "configs")
    wl = json.loads((DATA / "workloads" / "tiny.train.json").read_text())
    (tmp_path / "workloads" / "brand.new.json").write_text(json.dumps(wl))
    (tmp_path / "metrics" / "sweeps_seen.py").write_text(
        'UNIT = "sweeps"\n\ndef read(m):\n    return m.facts["sweeps"]\n')
    args = run.parse(["--workload", "brand.new", "--seed", "3",
                      "--seconds", "0.2", "--trace", "1"])
    out = run.execute(args, root=tmp_path, require_tpu=False,
                      interpret=None, cache=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["sweeps_seen"]["value"] == out["attempted"] >= 1
    assert out["metrics"]["layout_build_s"]["value"] > 0
    assert "ring_exposed_ms" not in out["metrics"]      # one chip
    assert list(out)[-2:] == ["checks", "_facts"]


def test_chain_seeds_take_any_whole_number():
    train = run.load_module(BENCH / "traffic" / "train.py")
    a = train.chain_seeds(2**40 + 3)
    assert a == train.chain_seeds(2**40 + 3)
    assert a != train.chain_seeds(3)
    assert 0 <= a[1] < 2**30


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nytimes.train",
         "--seed", str(2**40 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _run_cli(BENCH.parent)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
