"""The program's recorder (``repro.obs``) and what the trainer records.

The recorder's own contract (nesting, bound, counters, exceptions); the
spans sharing the profiler's clock; the layout's per-half work counts
against an independent recount; and ``NomadLDA.run``'s publish and
checkpoint spans, which leave the chain bit-identical.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.nomad import NomadLDA
from repro.data.sharding import build_layout, half_queue_split
from repro.data.synthetic import make_corpus


def _newest(name, n=1):
    return obs.spans(name)[-n:]


def test_span_nesting_and_parents():
    with obs.span("test.outer", tag=1) as attrs:
        with obs.span("test.inner"):
            pass
        attrs["added"] = 2
    inner, = _newest("test.inner")
    outer, = _newest("test.outer")
    assert inner.parent == "test.outer"
    assert outer.parent is None
    assert outer.attrs == {"tag": 1, "added": 2}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert outer.seconds >= inner.seconds >= 0
    assert outer.error is None


def test_span_kept_when_its_body_raises():
    with pytest.raises(KeyError):
        with obs.span("test.raises"):
            raise KeyError("x")
    span, = _newest("test.raises")
    assert span.error == "KeyError"
    with obs.span("test.after"):
        pass
    assert _newest("test.after")[0].parent is None     # stack unwound


def test_buffer_is_bounded():
    for i in range(obs.CAPACITY + 10):
        with obs.span("test.bounded", i=i):
            pass
    kept = obs.spans()
    assert len(kept) == obs.CAPACITY
    assert kept[-1].attrs["i"] == obs.CAPACITY + 9
    assert kept[0].attrs["i"] == 10


def test_counters_add_up():
    before = obs.counters().get("test.count", 0)
    obs.count("test.count")
    obs.count("test.count", 41)
    assert obs.counters()["test.count"] == before + 42


# -- the layout's per-half work ----------------------------------------------
def _corpus():
    corpus, _, _ = make_corpus(num_docs=40, vocab_size=120, num_topics=8,
                               mean_doc_len=20, seed=1)
    return corpus


LAYOUTS = {
    "dense": dict(n_workers=2, n_blocks=8, layout="dense"),
    "ragged": dict(n_workers=4, n_blocks=8, layout="ragged", tile=8),
    "ragged_paged": dict(n_workers=4, n_blocks=8, layout="ragged", tile=8,
                         doc_tile=8),
    "dense_paged": dict(n_workers=2, n_blocks=8, layout="dense",
                        doc_tile=8, doc_blk=8),
    "one_cell_queues": dict(n_workers=2, n_blocks=2, layout="ragged",
                            tile=8),
}


def _recount(lay):
    """(W_rounds, W, 2, 3) by slicing the token arrays queue by queue."""
    W, k = lay.W, lay.k
    k0 = half_queue_split(k)
    out = np.zeros((W, W, 2, 3), np.int64)
    for r in range(W):
        for w in range(W):
            c = (w + r) % W
            if lay.kind == "ragged":
                cut = (lay.tile_split if k0 else 0) * lay.tile
                halves = [np.s_[w, c, :cut], np.s_[w, c, cut:]]
            else:
                halves = [np.s_[w, c * k:c * k + k0],
                          np.s_[w, c * k + k0:(c + 1) * k]]
            for h, sl in enumerate(halves):
                valid, bound = lay.tok_valid[sl], lay.tok_bound[sl]
                if lay.kind == "ragged":
                    slots = valid.size
                else:                    # rows padded to the kernel's step
                    step = lay.doc_blk or 256
                    slots = valid.shape[0] * -(-valid.shape[-1] // step) \
                        * step
                out[r, w, h] = (valid.sum(), (valid & bound).sum(), slots)
    return out


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_half_work_recount(name):
    lay = build_layout(_corpus(), T=8, **LAYOUTS[name])
    work = lay.half_work()
    assert work.shape == (lay.W, lay.W, 2, 3)
    np.testing.assert_array_equal(work, _recount(lay))
    # a rebuild fires on every boundary: none sits on padding
    assert not (lay.tok_bound & ~lay.tok_valid).any()
    assert work[..., 0].sum() == lay.cell_sizes.sum()
    np.testing.assert_array_equal(lay.half_loads(), work[..., 0])
    if lay.k < 2:
        assert not work[:, :, 0].any()
    build = obs.spans("layout.build")[-1]
    assert build.attrs == {"tokens": int(work[..., 0].sum()),
                           "rebuilds": int(work[..., 1].sum()),
                           "slots": int(work[..., 2].sum())}


# -- the trainer's spans ------------------------------------------------------
@pytest.fixture(scope="module")
def trainer():
    lay = build_layout(_corpus(), n_workers=1, T=8, n_blocks=4,
                       layout="ragged", tile=8, doc_tile=8)
    mesh = jax.make_mesh((1,), ("worker",))
    return NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                    alpha=0.1, beta=0.01, inner_mode="fused",
                    ring_mode="pipelined", doc_tile=8)


def _host_events(log_dir, prefix):
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for p in data.planes if p.name.startswith("/host")
            for ln in p.lines for ev in ln.events
            if ev.name.startswith(prefix)]


def test_sweep_span_shares_the_profilers_clock(trainer, tmp_path):
    """Under a CPU profiler trace each ``nomad.sweep`` is a host event
    inside the caller's own annotation, as long as the recorder's span."""
    arrays = trainer.init_arrays(seed=0)
    arrays = jax.block_until_ready(trainer.sweep(arrays, seed=0))  # compile
    jax.profiler.start_trace(str(tmp_path))
    for s in (1, 2):
        with jax.profiler.TraceAnnotation("bench.sweep"):
            arrays = jax.block_until_ready(trainer.sweep(arrays, seed=s))
    jax.profiler.stop_trace()
    events = _host_events(str(tmp_path), ("nomad.", "bench."))
    sweeps = [e for e in events if e[0] == "nomad.sweep"]
    outer = [e for e in events if e[0] == "bench.sweep"]
    assert len(sweeps) == len(outer) == 2
    for (_, s0, s1), (_, b0, b1) in zip(sorted(sweeps, key=lambda e: e[1]),
                                        sorted(outer, key=lambda e: e[1])):
        assert b0 <= s0 <= s1 <= b1
    recorded = _newest("nomad.sweep", 2)
    assert [s.attrs["seed"] for s in recorded] == [1, 2]
    for (_, s0, s1), span in zip(sorted(sweeps, key=lambda e: e[1]),
                                 recorded):
        assert abs((s1 - s0) - (span.end_ns - span.start_ns)) < 1e6
        assert span.attrs["calls"] == 2            # two halves, one round
        assert span.attrs["worker_of"] == {int(jax.devices()[0].id): 0}
    # the layout's work rides by reference, the same array every sweep
    assert recorded[0].attrs["work"] is recorded[1].attrs["work"]
    np.testing.assert_array_equal(recorded[0].attrs["work"],
                                  trainer.layout.half_work())


def test_sweep_counters(trainer):
    arrays = trainer.init_arrays(seed=0)
    before = obs.counters()
    jax.block_until_ready(trainer.sweep(arrays, seed=0))
    after = obs.counters()
    work = trainer.layout.half_work()
    assert after["nomad.sweeps"] - before.get("nomad.sweeps", 0) == 1
    assert (after["nomad.tokens"] - before.get("nomad.tokens", 0)
            == work[..., 0].sum())
    assert (after["nomad.rebuilds"] - before.get("nomad.rebuilds", 0)
            == work[..., 1].sum())
    assert obs.spans("nomad.init_arrays")[-1].error is None


def test_run_spans_publish_and_checkpoint(trainer, tmp_path):
    """Publish every sweep and checkpoint every second: one span of each
    per event, tagged with its sweep, and the chain bit-identical to a
    run with neither and to sweeps called one by one."""
    plain, _ = trainer.run(4)
    chained = trainer.init_arrays(seed=0)
    for s in range(4):
        chained = trainer.sweep(chained, seed=s)
    published = []
    trainer.checkpoint_every = 2
    trainer.checkpoint_path = str(tmp_path / "chain.npz")
    try:
        hooked, _ = trainer.run(4, publish_every=1,
                                on_publish=published.append)
    finally:
        trainer.checkpoint_every = trainer.checkpoint_path = None
    for k in ("z", "n_td", "n_wt", "n_t"):
        np.testing.assert_array_equal(np.asarray(hooked[k]),
                                      np.asarray(plain[k]))
        np.testing.assert_array_equal(np.asarray(chained[k]),
                                      np.asarray(plain[k]))
    assert len(published) == 4
    assert [s.attrs["sweep"] for s in _newest("nomad.publish", 4)] == [
        0, 1, 2, 3]
    assert [s.attrs["sweep"] for s in _newest("nomad.checkpoint", 2)] == [
        1, 3]
    spans = obs.spans()
    last_run = spans[max(i for i, s in enumerate(spans)
                         if s.name == "nomad.init_arrays"):]
    assert [s.name for s in last_run].count("nomad.publish") == 4
    assert [s.name for s in last_run].count("nomad.checkpoint") == 2
