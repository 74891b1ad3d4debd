"""Online fold-in topic inference: the millions-of-users serving path.

DESIGN.md §10.  The trainer (``core/nomad.py``) owns the chain; serving
owns a *frozen* posterior-mean φ table.  Three pieces:

* :class:`PhiSnapshot` — an immutable, format-versioned φ table plus the
  hyperparameters and integrity digest needed to fold against it.
  Built from trained counts by :func:`snapshot_from_counts` (the same
  ``_phi_hat`` float ops as held-out evaluation) or loaded from the
  ``train/checkpoint.py:save_phi`` store.

* :func:`pack_docs` — ragged → padded: variable-length documents become
  a ``(D, L)`` tile (rows and columns bucketed to powers of two so the
  jit cache stays bounded) plus a validity mask.  Padded positions are
  provably inert under ``fold_in_batch``'s counter-mode RNG contract.

* :class:`LdaEngine` — double-buffered θ service.  ``publish`` builds
  the device-resident buffer *off* the serving path and installs it
  with one atomic reference swap (generation counter + content digest);
  ``query`` pins the buffer with a single attribute read, so a reader
  can never observe a torn or half-folded table even while a background
  ``NomadLDA.run(publish_every=...)`` ring keeps publishing.  Every
  answer carries the generation and digest it folded against, which is
  what ``launch/serve_check.py`` audits for torn reads.

Failure model (DESIGN.md §11): ``publish`` is the integrity gate — a
corrupt table raises :class:`SnapshotCorruptError`, a version skew
:class:`FormatVersionError`, and a snapshot whose source generation
(``meta["sweep"]``/``meta["generation"]``) would move the engine
*backwards* :class:`StaleGenerationError`; the live buffer keeps serving
through all three.  ``query`` runs behind admission control: a bounded
in-flight count sheds excess load (:class:`EngineOverloadedError`)
instead of queueing unboundedly, and a softer threshold degrades
answers (capped fold-in sweeps) before shedding starts — p99 stays
bounded because the engine refuses work it cannot finish in time.
:func:`fetch_snapshot` is the reader-side loader: bounded retry with
exponential backoff around transient damage (a publisher mid-write),
never around version skew.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.heldout import (_phi_hat, doc_fold_key, fold_in_batch,
                                theta_from_counts)
from repro.data.sharding import _pow2_ceil
from repro.kernels.fold_in import fold_in_fused
from repro.kernels.fused_sweep.ops import default_interpret
from repro.fault import fire as _fault_fire
from repro.fault.errors import (EngineOverloadedError, FormatVersionError,
                                SnapshotCorruptError, SnapshotDigestError,
                                StaleGenerationError)
from repro.train.checkpoint import (PHI_FORMAT_VERSION, load_phi, phi_digest,
                                    save_phi)

__all__ = ["PhiSnapshot", "snapshot_from_counts", "pack_docs",
           "TopicQuery", "TopicResult", "LdaEngine", "fetch_snapshot",
           "SnapshotCorruptError", "FormatVersionError",
           "StaleGenerationError", "EngineOverloadedError"]


# ---------------------------------------------------------------------------
# Snapshots
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PhiSnapshot:
    """A frozen φ table: ``phi`` is ``(J, T)`` f32, ``meta`` carries
    ``format_version``/``alpha``/``beta``/``J``/``T``/``digest`` (and any
    trainer-side extras, e.g. the sweep it was exported at)."""
    phi: np.ndarray
    meta: dict

    @property
    def alpha(self) -> float:
        return float(self.meta["alpha"])

    @property
    def beta(self) -> float:
        return float(self.meta["beta"])

    @property
    def digest(self) -> str:
        return self.meta["digest"]

    def save(self, path: str) -> None:
        save_phi(path, self.phi, self.meta)

    @classmethod
    def load(cls, path: str) -> "PhiSnapshot":
        phi, meta = load_phi(path)
        return cls(phi=phi, meta=meta)


def snapshot_from_counts(n_wt, n_t, *, alpha: float, beta: float,
                         extra_meta: dict | None = None) -> PhiSnapshot:
    """Freeze trained counts into a snapshot: φ̂ = (n_wt+β)/(n_t+Jβ),
    the identical float ops the held-out evaluator uses."""
    phi = np.asarray(_phi_hat(jnp.asarray(n_wt), jnp.asarray(n_t), beta),
                     np.float32)
    meta = dict(extra_meta or {})
    meta.update(format_version=PHI_FORMAT_VERSION,
                alpha=float(alpha), beta=float(beta),
                J=int(phi.shape[0]), T=int(phi.shape[1]),
                digest=phi_digest(phi))
    return PhiSnapshot(phi=phi, meta=meta)


def fetch_snapshot(path: str, *, retries: int = 3, backoff_s: float = 0.05,
                   max_backoff_s: float = 1.0,
                   sleep=time.sleep) -> PhiSnapshot:
    """Load a φ snapshot with bounded retry + exponential backoff
    (DESIGN.md §11) — the reader-side fetch a serving fleet points at a
    trainer's publish directory.

    Retried: ``FileNotFoundError`` (not published yet) and plain
    :class:`SnapshotCorruptError` (a publisher mid-write, a torn copy —
    transient by assumption, up to ``retries`` extra attempts, backoff
    doubling from ``backoff_s`` and capped at ``max_backoff_s``).
    **Never** retried: :class:`FormatVersionError` — a version skew is a
    deployment bug, and hammering the file cannot fix it — and
    :class:`SnapshotDigestError` — a digest/shape contradiction on a
    file that parsed end to end is proven-permanent damage (publishes
    rename atomically, so a complete parse rules out the mid-write
    race), and burning the backoff budget on it only delays the alarm.
    Each attempt fires the ``"serve.fetch"`` fault site (counter-indexed
    across calls), which is how the chaos harness makes the first N
    fetches fail deterministically."""
    if retries < 0:
        raise ValueError(f"retries must be >= 0, got {retries}")
    delay = backoff_s
    for attempt in range(retries + 1):
        try:
            _fault_fire("serve.fetch", path=path)
            return PhiSnapshot.load(path)
        except (FormatVersionError, SnapshotDigestError):
            raise
        except (FileNotFoundError, SnapshotCorruptError):
            if attempt == retries:
                raise
            sleep(delay)
            delay = min(delay * 2, max_backoff_s)
    raise AssertionError("unreachable")


# ---------------------------------------------------------------------------
# Ragged → padded batching
# ---------------------------------------------------------------------------
def pack_docs(docs, *, tile: int = 8):
    """Pack variable-length documents into a padded ``(D_pad, L)`` tile.

    ``L`` is the longest document rounded up to a multiple of ``tile``
    and then to a power-of-two tile count; ``D_pad`` is the doc count
    rounded to a power of two.  Both roundings bound the set of shapes
    the jitted fold-in kernel ever sees (same motivation as
    ``data/sharding.default_ragged_tile``: a handful of buckets instead
    of one compile per request).  Returns ``(word_ids, valid, n_real)``;
    padded positions and padded rows are all-False in ``valid`` and
    carry word id 0 — inert by `fold_in_batch`'s contract.
    """
    if tile < 1:
        raise ValueError(f"tile must be >= 1, got {tile}")
    docs = [np.asarray(d, np.int32).reshape(-1) for d in docs]
    if not docs:
        raise ValueError("pack_docs got an empty document list")
    n_real = len(docs)
    l_max = max(d.size for d in docs)
    n_tiles = _pow2_ceil(max(-(-l_max // tile), 1))
    L = n_tiles * tile
    D = _pow2_ceil(n_real)
    word_ids = np.zeros((D, L), np.int32)
    valid = np.zeros((D, L), bool)
    for i, d in enumerate(docs):
        word_ids[i, :d.size] = d
        valid[i, :d.size] = True
    return word_ids, valid, n_real


# ---------------------------------------------------------------------------
# Request / response types
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TopicQuery:
    """``docs``: variable-length token-id documents (empty docs allowed —
    their θ is the uniform α prior).  ``key``: base RNG key; document
    ``i`` of the query runs stream ``doc_fold_key(key, i)``, so a query
    over docs 0..D−1 is bit-reproducible by the serial ``fold_in`` under
    the same key.  ``sweeps`` overrides the engine default."""
    docs: tuple
    key: object = None
    sweeps: int | None = None


@dataclasses.dataclass(frozen=True)
class TopicResult:
    """θ rows for the query's documents plus the provenance needed to
    audit exactly which snapshot answered: generation + digest — and,
    under admission control, the load story (how many sweeps actually
    ran, whether this answer was degraded, cumulative shed/degraded
    counts at answer time)."""
    theta: np.ndarray        # (len(docs), T) f32, rows sum to 1
    n_td: np.ndarray         # (len(docs), T) int32 fold-in counts
    generation: int
    digest: str
    latency_s: float
    batch_shape: tuple       # padded (D_pad, L) actually swept
    sweeps_used: int = 0     # fold-in sweeps this answer ran
    degraded: bool = False   # True → sweeps were capped under overload
    shed_total: int = 0      # engine-lifetime queries shed so far
    degraded_total: int = 0  # engine-lifetime degraded answers so far


@dataclasses.dataclass(frozen=True)
class _Buffer:
    """One published φ buffer.  Immutable: a reader that grabbed this
    object sees a consistent (phi, alpha, generation, digest) forever,
    regardless of later publishes — the whole double-buffer protocol is
    `buf = self._buf` being a single atomic reference read."""
    phi: object              # device-resident (J, T) f32
    alpha: float
    generation: int
    digest: str
    meta: dict
    source: int | None = None  # trainer-side generation (meta sweep), the
                               #   monotonicity guard's comparison key


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("sweeps", "inner_mode", "interpret"))
def _theta_kernel(word_ids, valid, phi, alpha, doc_keys, sweeps, *,
                  inner_mode, interpret):
    if inner_mode == "fused":
        n_td = fold_in_fused(word_ids, valid, phi, alpha, doc_keys,
                             sweeps, interpret=interpret)
    else:
        n_td = fold_in_batch(word_ids, valid, phi, alpha, doc_keys, sweeps)
    return n_td, theta_from_counts(n_td, alpha)


def _bucket_len(n: int, tile: int) -> int:
    """The padded row length ``pack_docs`` would give a lone ``n``-token
    document — the pow-2 length bucket ``query`` groups by."""
    return _pow2_ceil(max(-(-n // tile), 1)) * tile


class LdaEngine:
    """Double-buffered fold-in θ service.

    Thread-safety contract: ``publish`` may run concurrently with any
    number of ``query`` calls.  Publishers serialize on a lock; readers
    take no lock at all — they pin the current :class:`_Buffer` with one
    reference read and use only that object, so a concurrent publish can
    reorder *which* snapshot answered but never mix two snapshots inside
    one answer.

    Admission control (DESIGN.md §11): ``max_pending`` bounds concurrent
    in-flight queries — excess load raises
    :class:`EngineOverloadedError` (shedding) instead of queueing
    unboundedly, which is what keeps p99 bounded under a flood.
    ``degrade_pending`` is the softer threshold: above it, answers still
    complete but with fold-in sweeps capped at ``degraded_sweeps``
    (graceful degradation before shedding).  Both default to ``None`` —
    no admission control, the pre-§11 behavior.

    ``inner_mode`` picks the fold-in implementation: ``"scan"`` (the
    vmapped ``lax.scan`` reference) or ``"fused"`` (the Pallas kernel,
    ``kernels/fold_in`` — bit-identical per document, DESIGN.md §10a).
    ``interpret=None`` resolves to compiled-on-TPU / interpreted
    elsewhere.  Queries are length-bucketed: docs whose pow-2 padded
    length (what ``pack_docs`` would give them alone) exceeds 4x the
    batch's median bucket dispatch in their own sub-batch, so one long
    outlier cannot inflate every row's padded sweep work — while
    ordinary mixed-length batches still run as a single dispatch.
    """

    def __init__(self, snapshot: PhiSnapshot | None = None, *,
                 sweeps: int = 20, tile: int = 8, max_batch: int = 64,
                 default_key=None, max_pending: int | None = None,
                 degrade_pending: int | None = None,
                 degraded_sweeps: int = 4, inner_mode: str = "scan",
                 interpret: bool | None = None):
        if sweeps < 1:
            raise ValueError(f"sweeps must be >= 1, got {sweeps}")
        if inner_mode not in ("scan", "fused"):
            raise ValueError(
                f"inner_mode must be 'scan' or 'fused', got {inner_mode!r}")
        if max_batch < 1 or max_batch & (max_batch - 1):
            raise ValueError(
                f"max_batch must be a power of two (jit-cache bucketing), "
                f"got {max_batch}")
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        if degrade_pending is not None and degrade_pending < 1:
            raise ValueError(
                f"degrade_pending must be >= 1, got {degrade_pending}")
        if degraded_sweeps < 1:
            raise ValueError(
                f"degraded_sweeps must be >= 1, got {degraded_sweeps}")
        self.sweeps = int(sweeps)
        self.tile = int(tile)
        self.max_batch = int(max_batch)
        self.inner_mode = inner_mode
        # Compiled on TPU, interpreted elsewhere (fused_sweep.ops) —
        # resolved once so every query hits the same jit bucket.
        self.interpret = (default_interpret() if interpret is None
                          else bool(interpret))
        self.max_pending = max_pending
        self.degrade_pending = degrade_pending
        self.degraded_sweeps = int(degraded_sweeps)
        self._default_key = (jax.random.key(0) if default_key is None
                             else default_key)
        self._publish_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._buf: _Buffer | None = None
        self._queries = 0
        self._pending = 0
        self._shed = 0
        self._degraded = 0
        self._rejected_publishes = 0
        self._max_pending_seen = 0
        if snapshot is not None:
            self.publish(snapshot)

    # -- publish side ------------------------------------------------------
    def _reject(self, exc: Exception):
        with self._stats_lock:
            self._rejected_publishes += 1
        raise exc

    def publish(self, snapshot: PhiSnapshot) -> int:
        """Install a new φ buffer; returns its generation.

        The integrity gate (DESIGN.md §11) — refuses, leaving the live
        buffer serving:

        * format-version mismatches (:class:`FormatVersionError`);
        * digest-mismatched tables (:class:`SnapshotCorruptError` — a
          corrupt φ must never reach readers);
        * geometry changes against the live buffer (``ValueError`` — a
          serving vocabulary cannot silently resize);
        * source-generation regressions (:class:`StaleGenerationError`):
          when both the live buffer's and the candidate's meta carry a
          trainer-side ordinal (``sweep``, else ``generation``), a
          candidate at or behind the live one is refused — a delayed or
          replayed publish cannot move readers backwards in time.

        The device transfer happens *before* the swap, so readers never
        wait on it.
        """
        ver = snapshot.meta.get("format_version")
        if ver != PHI_FORMAT_VERSION:
            self._reject(FormatVersionError(
                f"refusing φ snapshot format v{ver}; this engine serves "
                f"v{PHI_FORMAT_VERSION}"))
        phi = np.asarray(snapshot.phi, np.float32)
        if phi.ndim != 2:
            self._reject(SnapshotCorruptError(
                f"φ must be (J, T); got shape {phi.shape}"))
        digest = phi_digest(phi)
        if snapshot.meta.get("digest") not in (None, digest):
            self._reject(SnapshotCorruptError(
                "φ snapshot digest mismatch — refusing to serve a corrupt "
                "table"))
        src = snapshot.meta.get("sweep", snapshot.meta.get("generation"))
        src = None if src is None else int(src)
        phi_dev = jax.device_put(jnp.asarray(phi))
        jax.block_until_ready(phi_dev)
        with self._publish_lock:
            cur = self._buf
            if cur is not None and cur.phi.shape != phi.shape:
                self._reject(ValueError(
                    f"φ geometry change {cur.phi.shape} → {phi.shape}; "
                    f"drain and restart the engine to resize"))
            if (cur is not None and cur.source is not None
                    and src is not None and src <= cur.source):
                self._reject(StaleGenerationError(
                    f"φ snapshot source generation {src} would regress the "
                    f"live buffer's {cur.source}; refusing to move readers "
                    f"backwards"))
            gen = 1 if cur is None else cur.generation + 1
            self._buf = _Buffer(phi=phi_dev, alpha=snapshot.alpha,
                                generation=gen, digest=digest,
                                meta=dict(snapshot.meta), source=src)
        return gen

    @property
    def generation(self) -> int:
        buf = self._buf
        return 0 if buf is None else buf.generation

    # -- query side --------------------------------------------------------
    def _admit(self) -> bool:
        """Count this query in → whether it must run degraded.  Raises
        :class:`EngineOverloadedError` (shedding) when ``max_pending``
        concurrent queries are already in flight."""
        with self._stats_lock:
            pending = self._pending + 1
            if self.max_pending is not None and pending > self.max_pending:
                self._shed += 1
                raise EngineOverloadedError(
                    f"engine overloaded: {self._pending} queries in flight "
                    f"(max_pending={self.max_pending}); query shed — back "
                    f"off and retry")
            self._pending = pending
            self._max_pending_seen = max(self._max_pending_seen, pending)
            degraded = (self.degrade_pending is not None
                        and pending > self.degrade_pending)
            if degraded:
                self._degraded += 1
            return degraded

    def query(self, q: TopicQuery) -> TopicResult:
        buf = self._buf          # the one atomic read; pins the snapshot
        if buf is None:
            raise RuntimeError("LdaEngine has no published snapshot yet")
        t0 = time.perf_counter()
        docs = [np.asarray(d, np.int32).reshape(-1) for d in q.docs]
        if not docs:
            raise ValueError("TopicQuery carries no documents")
        J = buf.phi.shape[0]
        for i, d in enumerate(docs):
            if d.size and (int(d.min()) < 0 or int(d.max()) >= J):
                raise ValueError(
                    f"doc {i}: word ids out of range [0, {J}): "
                    f"[{d.min()}, {d.max()}]")
        key = self._default_key if q.key is None else q.key
        sweeps = self.sweeps if q.sweeps is None else int(q.sweeps)
        degraded = self._admit()
        if degraded:
            sweeps = min(sweeps, self.degraded_sweeps)
        try:
            T = buf.phi.shape[1]
            theta_out = np.empty((len(docs), T), np.float32)
            ntd_out = np.empty((len(docs), T), np.int32)
            shapes = []
            # Length-bucketed sub-batches: one outlier document must not
            # inflate L for every co-batched row (padded work is D_pad·L
            # per sweep).  Splitting is not free either — every group is
            # its own kernel dispatch — so only true outliers split off:
            # docs whose pow-2 length bucket stays within 4x the batch's
            # median bucket run as one group (padded to that group's
            # widest doc, the pre-split behaviour), and each bucket past
            # the cutoff dispatches on its own.  Per-doc bit-exactness
            # is unchanged: row RNG is keyed by the doc's *query* index
            # (batch-independent by the counter-mode contract), so the
            # grouping cannot perturb any row.
            blens = [_bucket_len(d.size, self.tile) for d in docs]
            cutoff = 4 * sorted(blens)[len(blens) // 2]
            main_L = max((b for b in blens if b <= cutoff), default=0)
            by_bucket: dict[int, list[int]] = {}
            for i, b in enumerate(blens):
                by_bucket.setdefault(b if b > cutoff else main_L,
                                     []).append(i)
            for _, idxs in sorted(by_bucket.items()):
                for lo in range(0, len(idxs), self.max_batch):
                    chunk = idxs[lo:lo + self.max_batch]
                    word_ids, valid, n_real = pack_docs(
                        [docs[i] for i in chunk], tile=self.tile)
                    # pad rows are all-invalid; their key index is inert
                    idx = np.asarray(
                        chunk + [chunk[-1]] * (word_ids.shape[0] - n_real),
                        np.int32)
                    doc_keys = jax.vmap(doc_fold_key, in_axes=(None, 0))(
                        key, jnp.asarray(idx))
                    n_td, theta = _theta_kernel(
                        jnp.asarray(word_ids), jnp.asarray(valid),
                        buf.phi, buf.alpha, doc_keys, sweeps,
                        inner_mode=self.inner_mode,
                        interpret=self.interpret)
                    jax.block_until_ready(theta)
                    theta_out[chunk] = np.asarray(theta)[:n_real]
                    ntd_out[chunk] = np.asarray(n_td)[:n_real]
                    shapes.append(word_ids.shape)
            with self._stats_lock:
                self._queries += 1
                shed_total, degraded_total = self._shed, self._degraded
        finally:
            with self._stats_lock:
                self._pending -= 1
        return TopicResult(
            theta=theta_out, n_td=ntd_out,
            generation=buf.generation, digest=buf.digest,
            latency_s=time.perf_counter() - t0,
            batch_shape=shapes[0] if len(shapes) == 1 else tuple(shapes),
            sweeps_used=sweeps, degraded=degraded,
            shed_total=shed_total, degraded_total=degraded_total)

    def stats(self) -> dict:
        """Engine-lifetime load/health counters (one consistent read)."""
        with self._stats_lock:
            return {
                "queries": self._queries,
                "pending": self._pending,
                "shed": self._shed,
                "degraded": self._degraded,
                "rejected_publishes": self._rejected_publishes,
                "max_pending_seen": self._max_pending_seen,
                "generation": self.generation,
            }
