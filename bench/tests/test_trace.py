"""The trace reduction, on traces recorded on TPU v5e chips — a traced
``nytimes.train`` window of two sweeps on one chip (seed 1001) and a
``pubmed.train.4chip`` window of one sweep on four (seed 5000000001) —
and on hand-made intervals."""
import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

import run

DATA = Path(__file__).resolve().parent / "data"
tr = run.load_module(run.BENCH / "trace.py")


def _recorded(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / "v5e.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return tr.load(str(path))


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    return _recorded(tmp_path_factory, "nytimes_train_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def ring_trace(tmp_path_factory):
    return _recorded(tmp_path_factory,
                     "pubmed_train_4chip_v5e.xplane.pb.gz")


def test_recorded_trace_reads(chip_trace):
    t = chip_trace
    assert list(t.devices) == [0]
    lo, hi = t.window()
    assert 28.6e9 < hi - lo < 28.8e9                 # the host's window
    busy = tr.busy_ns(t)[0]
    assert 0.99 < busy / (hi - lo) <= 1.0
    kernel = tr.named_ns(t, r"fused_sweep_")[0]
    assert 0.99 < kernel / busy <= 1.0
    assert sum(o.kind == "fused_sweep_ragged_docs_pallas"
               for o in t.devices[0]) == 4         # 2 sweeps x 2 halves
    bd = tr.breakdown(t)
    assert bd["device_ops"][0][0] == "fused_sweep_ragged_docs_pallas"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(g[1] >= 0 for g in bd["idle_gaps"])


def test_recorded_trace_metrics(chip_trace):
    """The per-layer readers on the recorded trace: 6,787,906 tokens in
    the window (two sweeps of 3,393,953)."""
    readers = run.metric_readers(run.BENCH)
    m = SimpleNamespace(
        trace=chip_trace, tracelib=tr, readers=readers,
        work=run.load_module(run.BENCH / "work.py"),
        facts={"T": 1024, "tokens": 3393953, "sweeps": 2,
               "tokens_per_chip": 6787906.0},
        spans={"layout_build": 1.74}, e2e={"train_tokens_per_s": 236729.0},
        chips=1, peak=tr.peak("TPU v5 lite"))
    us = readers["sweep_kernel_us_per_token"].read(m)
    assert 4.1 < us < 4.3
    roof = readers["sweep_kernel_roofline"].read(m)
    assert roof == pytest.approx(100 * 0.01508 / us, rel=0.01)
    assert 0 < readers["train_idle_share"].read(m) < 1
    assert readers["ring_exposed_ms"].read(m) is None       # one chip
    assert 0 < readers["train_mfu"].read(m) < 0.01
    assert readers["layout_build_s"].read(m) == 1.74


def test_recorded_ring_trace(ring_trace):
    """Four chips: the ring's ``lax.scan`` is a ``while`` whose event
    spans the round's kernels; it counts as busy time but hides no
    collective and is no operation of the breakdown."""
    t = ring_trace
    assert sorted(t.devices) == [0, 1, 2, 3]
    assert any(o.kind == "while" for o in t.devices[0])
    lo, hi = t.window()
    for d, ns in tr.exposed_ns(t).items():
        assert 0.3e9 < ns < 0.4e9, (d, ns)     # permute start + done
    for d, ns in tr.named_ns(t, r"fused_sweep_").items():
        assert 0.95 < ns / (hi - lo) < 1.0
    names = [n for n, _ in tr.breakdown(t)["device_ops"]]
    assert names[0] == "fused_sweep_ragged_docs_pallas"
    assert "while" not in names
    readers = run.metric_readers(run.BENCH)
    m = SimpleNamespace(trace=t, tracelib=tr, chips=4,
                        facts={"sweeps": 1, "tokens": 8035791})
    assert 300 < readers["ring_exposed_ms"].read(m) < 400


def _trace(ops, in_flight=(), window=(0, 100)):
    mk = lambda rows: [tr.Op(n, s, e) for n, s, e in rows]
    return tr.Trace(devices={0: mk(ops)}, in_flight={0: mk(in_flight)},
                    host=[tr.Op("bench.window", *window)])


def test_union_and_exposed_collectives():
    t = _trace([("fused_sweep_x.1", 0, 40), ("copy.2", 30, 50),
                ("collective-permute-done.1", 70, 80)],
               in_flight=[("collective-permute-start.1", 45, 80)])
    assert tr.busy_ns(t) == {0: 60}                 # [0, 50] + [70, 80]
    assert tr.named_ns(t, r"fused_sweep_") == {0: 40}
    # in flight 45..80; compute covers 45..50 -> exposed 30
    assert tr.exposed_ns(t) == {0: 30}
    assert tr.union_ns([(5, 10), (8, 20), (30, 40)], 0, 35) == 20


def test_window_needs_its_annotation():
    t = _trace([("fused_sweep_x.1", 0, 40)])
    t.host = []
    with pytest.raises(ValueError, match="bench.window"):
        t.window()


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        tr.peak("TPU v99")
