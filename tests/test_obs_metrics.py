"""The benchmark's readers of the program's spans (``bench/metrics``), on
the traces recorded on TPU v5e chips under ``bench/tests/data`` and on
hand-made ones.

The recorded traces predate the program's recorder, so each test records
the ``nomad.sweep`` spans the program would have: ``calls`` from the
configuration's ring, ``work`` as ``NomadLayout.half_work()`` read on a
chip for the configuration's layout (``pubmed_train_4chip_work.json``,
``nytimes_train_work.json``: a corpus generated on the CPU differs from
the chip's), and ``worker_of`` from the ring order ``jax.make_mesh``
gives a v5e 2x2 (device ids 0, 1, 3, 2).
"""
import gzip
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro import obs

BENCH = Path(__file__).resolve().parents[1] / "bench"
DATA = BENCH / "tests" / "data"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


run = _load(BENCH / "run.py", "bench_run_for_obs")
tr = run.load_module(BENCH / "trace.py")
readers = run.metric_readers(BENCH)
hop = readers["ring_hop_ms"]
fit = readers["sweep_rebuild_us"]

RING_2X2 = {0: 0, 1: 1, 3: 2, 2: 3}


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


@pytest.fixture(scope="module")
def ring_work():
    got = json.loads((DATA / "pubmed_train_4chip_work.json").read_text())
    return np.asarray(got["half_work"], np.int64)


class _Compiled(Exception):
    pass


def _record_sweeps(n, **attrs):
    """A failed warm-up sweep, then ``n`` sweeps, as the trainer records
    them in a benchmark run."""
    with pytest.raises(_Compiled):
        with obs.span("nomad.sweep", seed=0, **attrs):
            raise _Compiled
    for s in range(n):
        with obs.span("nomad.sweep", seed=s, **attrs):
            pass


def _m(trace, chips, sweeps, **facts):
    return SimpleNamespace(trace=trace, tracelib=tr, readers=readers,
                           facts=dict(sweeps=sweeps, **facts), spans={},
                           chips=chips, e2e={}, peak=tr.peak("TPU v5 lite"),
                           work=run.load_module(BENCH / "work.py"))


def test_ring_split_on_the_recorded_ring(ring_trace, ring_work):
    """PubMed, one sweep on four chips: the hops cost ≈ 17 ms, the chips
    wait ≈ 349 ms for one another, and together they are what
    ``ring_exposed_ms`` reads within 10%."""
    _record_sweeps(1, calls=8, work=ring_work, worker_of=RING_2X2)
    m = _m(ring_trace, 4, 1, tokens=8035791)
    hop_ms = readers["ring_hop_ms"].read(m)
    wait_ms = readers["ring_wait_ms"].read(m)
    exposed = readers["ring_exposed_ms"].read(m)
    assert 14 < hop_ms < 18
    assert 340 < wait_ms < 355
    assert abs(hop_ms + wait_ms - exposed) < 0.1 * exposed
    gaps = hop.step_gaps(ring_trace, obs.spans(), 1)
    assert gaps.shape == (1, 4, 8)
    assert (gaps >= 0).all()


def _ring_fit(ring_trace, ring_work):
    _record_sweeps(1, calls=8, work=ring_work, worker_of=RING_2X2)
    return fit.rebuild_fit(ring_trace, obs.spans(), 1, hop)


def test_rebuild_fit_on_the_recorded_ring(ring_trace, ring_work):
    """PubMed's 32 calls: a rebuild costs ≈ 11 µs and every slot, padding
    or token, ≈ 2.6 µs; rebuilds are ≈ 61% of the kernel's time."""
    got = _ring_fit(ring_trace, ring_work)
    assert got["r2"] > 0.999
    assert 10 < got["us_per_rebuild"] < 13
    assert 2.3 < got["us_per_slot"] < 2.8
    assert abs(got["us_per_token"]) < 0.1
    assert 0.55 < got["rebuild_share"] < 0.65
    m = _m(ring_trace, 4, 1, tokens=8035791)
    assert readers["sweep_rebuild_us"].read(m) == got["us_per_rebuild"]


def test_one_chip_reads_no_ring(chip_trace, ring_trace, ring_work):
    """NYTimes on one chip: no ring split, and two calls a sweep are too
    few work mixes for the fit.  The ring's fit predicts this chip's
    kernel time from its own layout's work to within 1%."""
    work = np.asarray(json.loads((DATA / "nytimes_train_work.json")
                                 .read_text())["half_work"], np.int64)
    costs = _ring_fit(ring_trace, ring_work)
    _record_sweeps(2, calls=2, work=work, worker_of={0: 0})
    m = _m(chip_trace, 1, 2, tokens=3393953, T=1024,
           tokens_per_chip=6787906.0)
    for name in ("ring_hop_ms", "ring_wait_ms", "sweep_rebuild_us"):
        assert readers[name].read(m) is None, name
    kernel_s = readers["sweep_kernel_us_per_token"].kernel_seconds(m) / 2
    tokens, rebuilds, slots = work.sum(axis=(0, 1, 2))
    predicted = (tokens * costs["us_per_token"] + slots * costs["us_per_slot"]
                 + rebuilds * costs["us_per_rebuild"]) / 1e6
    assert predicted == pytest.approx(kernel_s, rel=0.01)


def test_sweeps_chosen_by_recency(ring_trace, ring_work):
    """Spans of an earlier run in the same process, and a later failed
    sweep, do not shift the window's sweeps."""
    _record_sweeps(3, calls=6, work=ring_work, worker_of=RING_2X2)
    _record_sweeps(1, calls=8, work=ring_work, worker_of=RING_2X2)
    window = hop.window_sweeps(obs.spans(), 1)
    assert [s.attrs["calls"] for s in window] == [8]
    assert hop.window_sweeps(obs.spans(), 10**6) == []
    assert hop.window_sweeps(obs.spans(), None) == []


def test_init_arrays_reads_the_newest_span():
    with obs.span("nomad.init_arrays"):
        pass
    newest = obs.spans("nomad.init_arrays")[-1]
    m = _m(None, 1, 1)
    assert readers["init_arrays_s"].read(m) == newest.seconds


# -- hand-made traces ---------------------------------------------------------
W = 4
PLANTED = np.array([1.4, 20.0, 0.5])    # us per token, rebuild, slot


def _ring_trace(work, worker_of, sweeps, coef, noise=0.0, seed=0,
                hop_ns=1e6):
    """Four chips run their calls back to back, each hop a rendezvous of
    ``hop_ns`` after the slowest chip's call."""
    rng = np.random.default_rng(seed)
    devices = {d: [] for d in worker_of}
    host = []
    t = 1e6
    for _ in range(sweeps):
        start_sweep = t
        for r in range(W):
            for h in range(2):
                ends = []
                for d, w in worker_of.items():
                    us = work[r, w, h] @ coef * (1 + noise
                                                 * rng.standard_normal())
                    devices[d].append(tr.Op(f"fused_sweep_ragged_docs_h{h}.3",
                                            t, t + us * 1e3))
                    ends.append(t + us * 1e3)
                for d in devices:
                    devices[d].append(tr.Op("collective-permute-done.1",
                                            max(ends), max(ends) + hop_ns))
                t = max(ends) + hop_ns
        host.append(tr.Op("bench.sweep", start_sweep - 1e3, t + 1e3))
        t += 5e6
    host.insert(0, tr.Op("bench.window", 0, t))
    return tr.Trace(devices=devices, host=host)


def _work(seed=1):
    rng = np.random.default_rng(seed)
    work = np.zeros((W, W, 2, 3), np.int64)
    work[..., 0] = rng.integers(180_000, 310_000, (W, W, 2))
    work[..., 1] = rng.integers(90_000, 115_000, (W, W, 2))
    work[..., 2] = [314_880, 265_472]
    return work


def test_planted_rebuild_cost_is_recovered():
    work = _work()
    trace = _ring_trace(work, RING_2X2, 2, PLANTED, noise=1e-4)
    _record_sweeps(2, calls=8, work=work, worker_of=RING_2X2)
    got = fit.rebuild_fit(trace, obs.spans(), 2, hop)
    assert got["r2"] > 0.99
    assert got["us_per_rebuild"] == pytest.approx(20.0, rel=0.02)
    share = 20.0 * work[..., 1].sum() / (work @ PLANTED).sum()
    assert got["rebuild_share"] == pytest.approx(share, rel=0.02)
    # every hop here is a rendezvous 1 ms after the slowest chip
    hop_ms, wait_ms = hop.split_ms(trace, obs.spans(), 2)
    assert hop_ms == pytest.approx(8.0, abs=1e-6)
    assert wait_ms > 0


def test_fit_refuses_noise_and_too_few_mixes():
    work = _work()
    noisy = _ring_trace(work, RING_2X2, 1, PLANTED, noise=0.3, seed=3)
    _record_sweeps(1, calls=8, work=work, worker_of=RING_2X2)
    assert fit.rebuild_fit(noisy, obs.spans(), 1, hop) is None
    same = np.broadcast_to(work[:1, :1], work.shape).copy()
    flat = _ring_trace(same, RING_2X2, 1, PLANTED)
    _record_sweeps(1, calls=8, work=same, worker_of=RING_2X2)
    assert fit.fit(*fit.calls_work(flat, obs.spans(), 1, hop)) is None


def test_unknown_chip_reads_nothing():
    work = _work()
    trace = _ring_trace(work, RING_2X2, 1, PLANTED)
    _record_sweeps(1, calls=8, work=work, worker_of={0: 0, 1: 1})
    assert fit.rebuild_fit(trace, obs.spans(), 1, hop) is None
