"""Query synopsis: bounded store of past snippets + incremental model state.

Paper §2.3: per aggregate function g the synopsis retains at most C_g snippets
(LRU replacement). The covariance matrix Sigma_n (raw-answer covariances) and
its inverse are maintained *incrementally* in O(n^2 k) per blocked insert/evict
using the block matrix-inversion lemma — the same identity the paper's
Theorem 1 proof uses — with a periodic full refactor to bound numerical drift.

Serving (``improve``) runs against device-resident buffers padded to
**fill-level buckets** (powers of two, clamped to capacity) rather than to
capacity: one compiled program per bucket, and inference cost scales with the
actual synopsis fill instead of C_g^2. The new-snippet axis Q is bucketed the
same way, so a mixed-Q workload compiles one program per (Q-bucket,
fill-bucket) pair. Power-of-two buckets are mutually bitwise-consistent on the
XLA CPU/TPU dot paths (padding columns carry k=0 / Sigma^{-1}=I / alpha=0 and
contribute exact zeros), which is what the padding-invariance parity tests
pin down.

Learning never blocks serving: ``add`` snapshots the raw answers to host
memory and enqueues them on a background ingest thread (``_IngestQueue``)
which runs the covariance builds and blocked inverse updates off the critical
path. ``drain()`` is the explicit barrier; every reader of model state
(``improve``, ``state_dict``, ``refit``…) drains first, so the post-drain
state is bitwise identical to synchronous ingestion regardless of thread
timing — async ingest is deterministic by construction.

Failure never blocks serving either: a failed apply **quarantines** this
synopsis — the failed batch and everything after it are parked unapplied
(FIFO), ``drain()`` stays a plain barrier (it NEVER raises), ``improve``
returns the raw sample estimate (the paper's Theorem-1 floor — degraded but
honest), and ``state_dict`` refuses with a typed
``SynopsisQuarantinedError`` so a half-applied model never checkpoints.
``heal()`` restores a consistent model (from a last-good checkpoint state,
or a fresh ``rebuild()`` from the row arrays), replays the parked batches in
order, and rejoins serving — for failures injected at the apply seam
(``repro.ft.faults``) the healed state is bitwise-identical to a
never-failed store.
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import threading
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import covariance, inference, learning, validation
from repro.ft import faults
from repro.core.types import (
    FREQ,
    GPParams,
    ImprovedAnswer,
    RawAnswer,
    Schema,
    SnippetBatch,
    bucket_size,
    pad_snippets,
    snippet_key,
)

REFACTOR_EVERY = 128  # full O(n^3) rebuild cadence (numerical hygiene)
JITTER = 1e-10

# Smallest serve-path tiles: fills/batches below these share one program.
MIN_FILL_BUCKET = 8
MIN_Q_BUCKET = 8


def model_device(device=None):
    """The CPU device that runs a synopsis' f64 model math, or None.

    The model math is the SE covariance build (``covariance``), the blocked
    inverse updates (``inv_append_block``'s ``eigh``,
    ``inv_delete_block``'s ``solve``), the Cholesky refactor and the refit.
    It stays f64 (see ``repro.core.types``). XLA:TPU takes 67 s to compile
    the f64 covariance for one (64, 512) shape and 137 s for the improve
    inputs at (128, 2048), and it refuses f64 ``solve`` (LU is f32-only).
    So when the serving device is not a CPU, this math runs on the host
    CPU device, in f64: the same programs, the same bits as the CPU build.
    None means the serving device is a CPU and nothing moves.
    """
    platform = device.platform if device is not None else jax.default_backend()
    return None if platform == "cpu" else jax.devices("cpu")[0]


def inv_append_block(ainv, cols, block, jitter=JITTER):
    """O(m^2 k + k^3) inverse update appending k rows/cols at once.

    Blocked matrix-inversion lemma (rank-k): given A^{-1} for the current
    (m, m) covariance, the inverse of [[A, Bᵀ], [B, D]] is assembled from the
    Schur complement S = D - B A^{-1} Bᵀ.

    cols:  (k, m) covariance of the new rows against the existing ones (B).
    block: (k, k) covariance among the new rows, noise included on the
           diagonal (D).
    """
    k = block.shape[0]
    m = ainv.shape[0]
    u = cols @ ainv  # (k, m) = B A^{-1}
    s = block - u @ cols.T  # Schur complement
    s = 0.5 * (s + s.T)
    # Clamp to PSD via eigenvalues — the rank-k generalization of the scalar
    # max(s, jitter): near-duplicate snippets can make S numerically
    # indefinite, and jnp's Cholesky would silently emit NaNs.
    w, v = jnp.linalg.eigh(s)
    w = jnp.maximum(w + jitter, jitter)
    sinv = (v / w) @ v.T
    ust = u.T @ sinv  # (m, k) = A^{-1} Bᵀ S^{-1}
    out = jnp.zeros((m + k, m + k), ainv.dtype)
    out = out.at[:m, :m].set(ainv + ust @ u)
    out = out.at[:m, m:].set(-ust)
    out = out.at[m:, :m].set(-ust.T)
    out = out.at[m:, m:].set(sinv)
    return out


def inv_delete_block(ainv, positions):
    """O(m^2 k + k^3) inverse update deleting k rows/cols at once.

    Partitioned-inverse identity: with the inverse partitioned over
    keep/delete index sets as [[P, Q], [Qᵀ, R]], the inverse of the kept
    block of the original matrix is P - Q R^{-1} Qᵀ.
    """
    n = ainv.shape[0]
    pos = np.asarray(positions, np.int64)
    keep = np.setdiff1d(np.arange(n), pos)
    a = ainv[np.ix_(keep, keep)]
    b = ainv[np.ix_(keep, pos)]
    d = ainv[np.ix_(pos, pos)]
    return a - b @ jnp.linalg.solve(d, b.T)


def _improve_inputs(past: SnippetBatch, valid, params: GPParams, new: SnippetBatch):
    """Covariance inputs of the improve step: (k_mat, kappa2, mu_new)."""
    k_mat = covariance.cov_matrix(new, past, params) * valid[None, :]
    kappa2 = covariance.cov_diag(new, params)
    mu_new = covariance.prior_mean(new, params)
    return k_mat, kappa2, mu_new


def _improve_core(
    past: SnippetBatch,
    valid,
    sigma_inv,
    alpha,
    params: GPParams,
    new: SnippetBatch,
    raw_theta,
    raw_beta2,
    delta_v,
):
    """Improve Q new snippets against one padded synopsis state (Eq. 11/12 + App. B)."""
    k_mat, kappa2, mu_new = _improve_inputs(past, valid, params, new)
    model_theta, model_beta2, gamma2 = inference.model_based_answer(
        k_mat, kappa2, sigma_inv, alpha, mu_new, raw_theta, raw_beta2
    )
    theta, beta2, accepted = validation.validate(
        new.agg, model_theta, model_beta2, raw_theta, raw_beta2, delta_v
    )
    return theta, beta2, accepted


# One compiled program per (Q-bucket, fill-bucket) shape pair.
_improve_padded = jax.jit(_improve_core)
# Stacked variant: one dispatch improves G aggregate-function groups at once
# (leading axis over synopses). Bitwise equal per slice to the single-group
# program — batched dots reduce in the same order as unbatched ones.
_improve_stacked = jax.jit(
    jax.vmap(_improve_core, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None))
)
_improve_inputs_jit = jax.jit(_improve_inputs)


def _pad_raw(x, target: int, fill: float):
    """Pad a 1-D raw-answer vector up to the Q bucket (host-side, f64)."""
    x = jnp.asarray(x)
    k = target - x.shape[0]
    if k <= 0:
        return x
    return jnp.concatenate([x, jnp.full((k,), fill, x.dtype)])


# Ingest threads must be quiescent when the interpreter tears down: a worker
# still inside an XLA dispatch at exit aborts the C++ runtime. atexit runs
# before teardown, so draining here leaves the daemon threads parked in plain
# condition waits.
_LIVE_QUEUES: "weakref.WeakSet" = weakref.WeakSet()


@atexit.register
def _drain_live_queues():
    for q in list(_LIVE_QUEUES):
        try:
            q.drain()
        except Exception:
            pass


MAX_PENDING_DEFAULT = 64  # ingest back-pressure bound (pending batches)


class SynopsisQuarantinedError(RuntimeError):
    """Raised by ``state_dict`` on a quarantined synopsis: a model built on a
    half-applied batch must never checkpoint. Serving paths never raise this —
    they degrade to the raw sample estimate instead (Theorem 1's floor)."""

    def __init__(self, name: Optional[str], cause: BaseException):
        super().__init__(
            f"synopsis {name or '<unnamed>'} is quarantined "
            f"(heal() to rejoin): {cause!r}"
        )
        self.name = name
        self.cause = cause


class _IngestQueue:
    """Background applier for ``Synopsis.add`` batches.

    Batches are applied strictly in submission order, one at a time, so the
    post-``drain()`` state is bitwise identical to synchronous ingestion no
    matter how worker progress interleaves with serving. Wakeups coalesce:
    one lock round hands the worker every batch queued since the last one.
    The worker thread is daemonic, starts lazily, and exits after an idle
    period (``submit`` restarts it on demand).

    The queue is BOUNDED (``max_pending`` batches): ``try_submit`` refuses
    new work while the worker is that far behind, and the caller sheds to
    synchronous ingestion (drain, then apply inline — FIFO order and hence
    bitwise determinism are preserved). ``high_water`` records the deepest
    backlog observed, so operators can see how close serving runs to the
    bound.

    Failure handling lives in the apply fn, not here: the queue's applier is
    ``Synopsis._guarded_apply``, which never raises — a failed apply
    quarantines the owning synopsis and parks the failed batch (and every
    later one) for ``heal()`` replay. ``drain()`` is therefore ALWAYS a
    plain barrier: it waits for the backlog and never re-raises, so one bad
    batch can no longer poison every subsequent barrier globally.
    """

    IDLE_TIMEOUT = 5.0

    def __init__(self, apply_fn, max_pending: int = MAX_PENDING_DEFAULT):
        self._apply = apply_fn
        self.max_pending = int(max_pending)
        self.high_water = 0
        self._pending: collections.deque = collections.deque()
        self._cv = threading.Condition()
        self._outstanding = 0
        self._thread: Optional[threading.Thread] = None
        _LIVE_QUEUES.add(self)

    def try_submit(self, item) -> bool:
        """Enqueue unless the backlog is at the bound; False means shed."""
        with self._cv:
            if self._outstanding >= self.max_pending:
                return False
            self._pending.append(item)
            self._outstanding += 1
            self.high_water = max(self.high_water, self._outstanding)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="synopsis-ingest", daemon=True
                )
                self._thread.start()
            self._cv.notify_all()
            return True

    def _run(self):
        while True:
            with self._cv:
                while not self._pending:
                    woke = self._cv.wait(timeout=self.IDLE_TIMEOUT)
                    if not woke and not self._pending:
                        self._thread = None  # idle exit; submit() restarts
                        return
                batch = list(self._pending)
                self._pending.clear()
            for item in batch:
                try:
                    self._apply(*item)
                except BaseException:  # noqa: BLE001 — guarded appliers
                    pass  # never raise out of the worker; the guarded apply
                    # already quarantined the synopsis
                with self._cv:
                    self._outstanding -= 1
                    self._cv.notify_all()

    def drain(self):
        """Barrier only: wait until the backlog is fully handed to the
        applier. Never raises (see class docstring)."""
        with self._cv:
            while self._outstanding:
                self._cv.wait()


class Synopsis:
    """Bounded per-aggregate-function snippet store + incremental GP state.

    ``device``: optional JAX device the model state (serve buffers and the
    incremental Sigma^{-1} chain) is committed to — the placement hook the
    ``ShardedSynopsisStore`` uses to spread aggregate keys over a mesh.
    ``min_fill_bucket``/``min_q_bucket``: smallest serve-path tiles
    (``EngineConfig`` lifts these per deployment; defaults unchanged).
    """

    def __init__(
        self,
        schema: Schema,
        capacity: int = 2000,
        delta_v: float = 0.99,
        params: Optional[GPParams] = None,
        async_ingest: bool = True,
        max_pending: int = MAX_PENDING_DEFAULT,
        device=None,
        min_fill_bucket: int = MIN_FILL_BUCKET,
        min_q_bucket: int = MIN_Q_BUCKET,
    ):
        self.schema = schema
        self.capacity = int(capacity)
        self.delta_v = float(delta_v)
        self.async_ingest = bool(async_ingest)
        self.max_pending = int(max_pending)
        self.device = device
        self._host = model_device(device)
        self.min_fill_bucket = int(min_fill_bucket)
        self.min_q_bucket = int(min_q_bucket)
        self.name: Optional[str] = None  # store-assigned state_key (fault key)
        # Monotone state-generation counter for cache invalidation
        # (repro.intel): bumped SYNCHRONOUSLY on the caller thread at every
        # state transition that can change served answers — add() at enqueue
        # time (before the async apply, so staleness is deterministic even
        # under async ingest), quarantine, heal, refit, append adjustment and
        # state restore. A cached answer records the generations it was
        # derived under; any mismatch marks it stale.
        self.generation = 0
        self._shed_count = 0
        self._restored_high_water = 0
        self._qlock = threading.Lock()
        self._quarantine_exc: Optional[BaseException] = None
        self._unapplied: list = []  # parked (FIFO) batches awaiting heal()
        self._quarantine_count = 0  # quarantine episodes over this lifetime
        l, c, v = schema.n_num, schema.n_cat, max(schema.cat_vmax, 1)
        C = self.capacity
        self._lo = np.zeros((C, l))
        self._hi = np.ones((C, l))
        self._cat = np.ones((C, c, v), dtype=bool)
        self._agg = np.full((C,), FREQ, np.int32)
        self._measure = np.zeros((C,), np.int32)
        self._theta = np.zeros((C,))
        self._beta2 = np.ones((C,))
        self._stamp = np.full((C,), -1, np.int64)
        self.n = 0
        self._clock = 0
        self._keys: dict = {}
        self.params = params or GPParams.init(schema)
        self._sigma = np.zeros((C, C))
        self._sigma_inv = self._model_put(jnp.zeros((0, 0)))
        self._alpha = self._model_put(jnp.zeros((0,)))
        self._updates_since_refactor = 0
        self._order: list = []  # row ids in Sigma^{-1} ordering
        self._device_states: dict = {}  # fill bucket -> padded serve buffers
        self._ingest: Optional[_IngestQueue] = None

    # -------------------------------------------------------------- placement
    def _put(self, x):
        """Commit an array (or pytree) to this synopsis' device.

        With ``device=None`` this is a plain ``jnp`` conversion on the
        default device — the historical behavior. All CPU host devices run
        the same compiled programs, so placement never changes answers
        bitwise; it only changes where the FLOPs land.
        """
        if self.device is None:
            return jax.tree.map(jnp.asarray, x)
        return jax.device_put(x, self.device)

    def _model_put(self, x):
        """Commit model state (Sigma^{-1}, alpha) to where the model math
        runs: the host CPU device off-CPU (``model_device``), else ``_put``."""
        if self._host is None:
            return self._put(x)
        return jax.device_put(x, self._host)

    def _on_host(self):
        """Scope in which the f64 model math runs (``model_device``)."""
        if self._host is None:
            return contextlib.nullcontext()
        return jax.default_device(self._host)

    # ---------------------------------------------------------------- storage
    def _row_batch(self, rows) -> SnippetBatch:
        return SnippetBatch(
            lo=jnp.asarray(self._lo[rows]),
            hi=jnp.asarray(self._hi[rows]),
            cat=jnp.asarray(self._cat[rows]),
            agg=jnp.asarray(self._agg[rows]),
            measure=jnp.asarray(self._measure[rows]),
        )

    def active(self) -> SnippetBatch:
        self.drain()
        return self._row_batch(np.arange(self.n))

    def theta(self):
        self.drain()
        return jnp.asarray(self._theta[: self.n])

    def beta2(self):
        self.drain()
        return jnp.asarray(self._beta2[: self.n])

    @staticmethod
    def _key(lo, hi, cat, agg, measure):
        return snippet_key(lo, hi, cat, agg, measure)

    # ----------------------------------------------------------------- ingest
    def add(self, snippets: SnippetBatch, theta, beta2):
        """Insert raw answers; duplicates refresh LRU stamps and keep the more
        accurate answer.

        The host snapshot happens here (cheap copies); the covariance builds
        and blocked inverse updates run on the background ingest thread so
        callers return as soon as the answers are enqueued. ``drain()`` is
        the barrier; batches apply strictly in FIFO order, so the post-drain
        state is bitwise identical to synchronous ingestion
        (``async_ingest=False`` applies inline instead).

        Back-pressure: the ingest queue holds at most ``max_pending``
        batches. Under overload the caller sheds to synchronous ingestion —
        drain the backlog, then apply this batch inline — which bounds host
        memory and keeps FIFO order (determinism) intact.
        """
        # Bump BEFORE the (possibly async) apply: callers observe the new
        # generation at enqueue time, so an answer cached right after this
        # add() records the post-ingest generation deterministically, and a
        # failing apply (→ quarantine) can never serve a pre-failure cached
        # answer as fresh — the entry was already staleness-bumped here.
        self.generation += 1
        item = (
            np.array(np.asarray(snippets.lo), dtype=np.float64),
            np.array(np.asarray(snippets.hi), dtype=np.float64),
            np.array(np.asarray(snippets.cat), dtype=bool),
            np.array(np.asarray(snippets.agg), dtype=np.int32),
            np.array(np.asarray(snippets.measure), dtype=np.int32),
            np.array(np.asarray(theta), dtype=np.float64),
            np.array(np.asarray(beta2), dtype=np.float64),
        )
        if not self.async_ingest:
            self._guarded_apply(*item)
            return
        if self._ingest is None:
            self._ingest = _IngestQueue(self._guarded_apply,
                                        max_pending=self.max_pending)
        if not self._ingest.try_submit(item):
            self._shed_count += 1
            self._ingest.drain()  # preserve FIFO before applying inline
            self._guarded_apply(*item)

    def _guarded_apply(self, *item):
        """Apply one batch, quarantining on failure instead of raising.

        This is the ONLY applier the ingest queue (and the sync/shed paths)
        run, so a failed covariance build / inverse update can never
        propagate out of ``add``/``drain``: the synopsis quarantines, the
        failed batch and everything after it park in FIFO order for
        ``heal()`` replay, and serving continues on the raw-answer floor.
        """
        with self._qlock:
            if self._quarantine_exc is not None:
                self._unapplied.append(item)
                return
        try:
            with self._on_host(), TraceAnnotation("verdict.ingest"):
                self._apply_add(*item)
        except BaseException as e:  # noqa: BLE001 — quarantine, never raise
            self._mark_quarantined(e, item)

    def _mark_quarantined(self, exc: BaseException, item=None):
        with self._qlock:
            if self._quarantine_exc is None:
                self._quarantine_exc = exc
                self._quarantine_count += 1
                self.generation += 1  # degraded: cached answers go stale
            if item is not None:
                self._unapplied.append(item)

    @property
    def quarantined(self) -> bool:
        """Whether this synopsis is serving degraded (raw answers only)."""
        return self._quarantine_exc is not None

    @property
    def quarantine_reason(self) -> Optional[str]:
        exc = self._quarantine_exc
        return None if exc is None else repr(exc)

    def heal(self, state: Optional[dict] = None) -> bool:
        """Rebuild a consistent model and rejoin serving.

        ``state``: a last-good ``state_dict`` snapshot (e.g. from
        ``CheckpointManager.restore_blind``) to restore from; ``None``
        rebuilds Sigma / Sigma^{-1} / alpha from this synopsis' own row
        arrays (``rebuild()``), which is exact when the failure struck at
        the apply seam *before* any mutation (all ``repro.ft.faults``
        injections do). Parked batches then replay in their original FIFO
        order, so a healed synopsis is bitwise-identical to one that never
        failed. Returns True iff the synopsis is healthy afterwards; a
        replay failure re-quarantines (remaining batches stay parked) and
        returns False. Call from a quiesced serving thread — concurrent
        ``add`` during heal can reorder replay.
        """
        if not self.quarantined:
            return True
        if self._ingest is not None:
            # Flush in-flight adds into the parked list while the flag is
            # still set (the guarded applier parks rather than applies).
            self._ingest.drain()
        with self._qlock:
            parked = list(self._unapplied)
            self._unapplied.clear()
            self._quarantine_exc = None
        try:
            if state is not None:
                self.load_state_dict(state)
            else:
                self.rebuild()
        except BaseException as e:  # noqa: BLE001 — re-quarantine
            with self._qlock:
                self._quarantine_exc = e
                self._quarantine_count += 1
                self._unapplied = parked + self._unapplied
            return False
        for i, item in enumerate(parked):
            try:
                with self._on_host():
                    self._apply_add(*item)
            except BaseException as e:  # noqa: BLE001 — re-quarantine
                with self._qlock:
                    self._quarantine_exc = e
                    self._quarantine_count += 1
                    self._unapplied = parked[i:] + self._unapplied
                return False
        self.generation += 1  # healed state ≠ the state cached answers saw
        return True

    def drain(self):
        """Barrier: block until every enqueued ``add`` batch has been handed
        to the (never-raising) guarded applier. NEVER raises — an ingest
        failure quarantines this synopsis instead of poisoning the barrier.
        Idempotent and cheap when idle."""
        try:
            faults.fire("store.drain", key=self.name)
        except BaseException as e:  # noqa: BLE001 — injected barrier fault
            self._mark_quarantined(e)  # still quiesce the worker below
        if self._ingest is not None:
            self._ingest.drain()

    @property
    def ingest_high_water(self) -> int:
        """Deepest async-ingest backlog observed (batches), incl. restored."""
        live = self._ingest.high_water if self._ingest is not None else 0
        return max(live, self._restored_high_water)

    def ingest_stats(self) -> dict:
        """Back-pressure + quarantine telemetry for the ingest path."""
        with self._qlock:
            return {
                "max_pending": self.max_pending,
                "high_water": self.ingest_high_water,
                "shed_count": self._shed_count,
                "quarantined": self._quarantine_exc is not None,
                "quarantine_reason": (
                    None if self._quarantine_exc is None
                    else repr(self._quarantine_exc)
                ),
                "unapplied": len(self._unapplied),
                "quarantine_count": self._quarantine_count,
            }

    def _apply_add(self, lo, hi, cat, agg, mea, theta, beta2):
        """Synchronous ingest of one host-side batch (runs on the worker).

        Vectorized: covariance columns for every genuinely-new row are built
        in one ``cov_matrix_jit`` call and applied with one blocked rank-k
        inverse update (``inv_append_block``); capacity evictions for the
        whole batch are applied with one blocked delete. Dedup/LRU semantics
        match the historical per-snippet path, except that eviction victims
        are chosen after the whole incoming batch has refreshed its duplicate
        stamps.
        """
        faults.fire("ingest.apply", key=self.name)  # seam: before any mutation
        pending: dict = {}  # key -> [incoming index of best beta2, LRU stamp]
        for i in range(lo.shape[0]):
            if not (np.isfinite(theta[i]) and np.isfinite(beta2[i])):
                continue
            key = self._key(lo[i], hi[i], cat[i], agg[i], mea[i])
            self._clock += 1
            if key in self._keys:
                r = self._keys[key]
                self._stamp[r] = self._clock
                if beta2[i] < self._beta2[r]:
                    self._theta[r] = theta[i]
                    self._replace_beta(r, beta2[i])
                continue
            entry = pending.get(key)
            if entry is None:
                pending[key] = [i, self._clock]
            else:
                entry[1] = self._clock
                if beta2[i] < beta2[entry[0]]:
                    entry[0] = i
        # If one call brings more new snippets than the whole store holds,
        # only the most recently used ``capacity`` survive (LRU: a snippet
        # re-occurring late in the batch carries its refreshed stamp).
        new = list(pending.items())
        if len(new) > self.capacity:
            new.sort(key=lambda kv: kv[1][1])
            new = new[-self.capacity :]
        if new:
            n_evict = max(0, self.n + len(new) - self.capacity)
            free: list = []
            if n_evict:
                victims = np.argsort(self._stamp[: self.n], kind="stable")[:n_evict]
                for r in victims:
                    old_key = self._key(
                        self._lo[r], self._hi[r], self._cat[r],
                        self._agg[r], self._measure[r],
                    )
                    self._keys.pop(old_key, None)
                self._delete_block_from_model(victims)
                free = [int(r) for r in victims]
            grow = len(new) - len(free)
            slots = list(range(self.n, self.n + grow)) + free
            self.n += grow
            for (key, (i, stamp)), r in zip(new, slots):
                self._lo[r] = lo[i]
                self._hi[r] = hi[i]
                self._cat[r] = cat[i]
                self._agg[r] = agg[i]
                self._measure[r] = mea[i]
                self._theta[r] = theta[i]
                self._beta2[r] = beta2[i]
                self._stamp[r] = stamp
                self._keys[key] = r
            self._insert_block_into_model(slots)
        self._refresh_alpha()
        self._device_states.clear()

    def _replace_beta(self, r, new_beta2):
        """Diagonal-only change: redo row r in the model (delete+insert)."""
        self._delete_block_from_model([r])
        self._beta2[r] = new_beta2
        self._insert_block_into_model([r])

    # ------------------------------------------------------ incremental model
    def _cov_blocks(self, rows, prev):
        """Covariance of ``rows`` against ``prev`` and among themselves.

        Inputs are padded to shape buckets so ``cov_matrix_jit`` compiles a
        bounded number of programs instead of one per synopsis fill level.
        """
        k = len(rows)
        batch = self._row_batch(np.asarray(rows, np.int64))
        padded = pad_snippets(batch, 8)
        if len(prev):
            prev_b = pad_snippets(self._row_batch(np.asarray(prev, np.int64)), 64)
            cols = np.asarray(
                covariance.cov_matrix_jit(padded, prev_b, self.params)
            )[:k, : len(prev)]
        else:
            cols = np.zeros((k, 0))
        block = np.array(
            covariance.cov_matrix_jit(padded, padded, self.params)
        )[:k, :k]
        block[np.diag_indices(k)] = (
            np.asarray(covariance.cov_diag_jit(padded, self.params))[:k]
            + self._beta2[np.asarray(rows, np.int64)]
        )
        return cols, block

    def _insert_block_into_model(self, rows):
        """Rows were just written into free/evicted slots; append them to the
        model in one blocked update.

        The inverse is maintained over the *ordering* [active rows]; we keep a
        permutation-free scheme by always appending logically: position in the
        inverse == position in ``self._order``.
        """
        rows = [int(r) for r in rows]
        prev = list(self._order)
        cols, block = self._cov_blocks(rows, prev)
        self._sigma[np.ix_(rows, prev)] = cols
        self._sigma[np.ix_(prev, rows)] = cols.T
        self._sigma[np.ix_(rows, rows)] = block
        self._updates_since_refactor += len(rows)
        self._order.extend(rows)
        if self._updates_since_refactor >= REFACTOR_EVERY:
            self._refactor()
            return
        self._sigma_inv = inv_append_block(
            self._sigma_inv, jnp.asarray(cols), jnp.asarray(block)
        )

    def _delete_block_from_model(self, rows):
        members = set(self._order)
        rows = [int(r) for r in rows if int(r) in members]
        if not rows:
            return
        pos = sorted(self._order.index(r) for r in rows)
        self._sigma_inv = inv_delete_block(self._sigma_inv, pos)
        for p in reversed(pos):
            self._order.pop(p)
        self._updates_since_refactor += len(pos)

    def _refactor(self):
        """Full O(n^3) rebuild of Sigma^{-1} from Sigma (numerical hygiene)."""
        rows = np.asarray(self._order, dtype=np.int64)
        if len(rows) == 0:
            self._sigma_inv = self._model_put(jnp.zeros((0, 0)))
            self._updates_since_refactor = 0
            return
        sig = self._model_put(self._sigma[np.ix_(rows, rows)])
        chol = inference.factorize(sig, JITTER)
        self._sigma_inv = inference.inverse_from_chol(chol)
        self._updates_since_refactor = 0

    def _refresh_alpha(self):
        rows = np.asarray(self._order, dtype=np.int64)
        if len(rows) == 0:
            self._alpha = self._model_put(jnp.zeros((0,)))
            return
        batch = self._row_batch(rows)
        resid = jnp.asarray(self._theta[rows]) - covariance.prior_mean(batch, self.params)
        self._alpha = self._sigma_inv @ resid

    # ------------------------------------------------------------------ refit
    def refit(self, steps: int = 150, lr: float = 0.1, learn_sigma: bool = False):
        """Offline learning (Appendix A): relearn params, rebuild the model.

        A quarantined synopsis skips refit (no-op): the row arrays may hold a
        half-applied batch, so learning waits for ``heal()``.
        """
        self.drain()
        if self.quarantined or self.n < 3:
            return self.params
        rows = np.asarray(self._order, dtype=np.int64)
        with self._on_host():
            batch = self._row_batch(rows)
            theta = jnp.asarray(self._theta[rows])
            beta2 = jnp.asarray(self._beta2[rows])
            self.params, _ = learning.fit(
                batch, theta, beta2, self.schema, steps=steps, lr=lr,
                learn_sigma=learn_sigma,
            )
        self.rebuild()
        self.generation += 1  # relearned params change improved answers
        return self.params

    def rebuild(self):
        """Recompute Sigma for the current params, refactor, refresh alpha."""
        self.drain()
        rows = np.asarray(self._order, dtype=np.int64)
        with self._on_host():
            if len(rows):
                batch = self._row_batch(rows)
                sig = np.array(
                    covariance.cov_matrix_jit(batch, batch, self.params))
                sig[np.diag_indices(len(rows))] = np.asarray(
                    covariance.cov_diag_jit(batch, self.params)
                ) + self._beta2[rows]
                self._sigma[np.ix_(rows, rows)] = sig
            self._refactor()
            self._refresh_alpha()
        self._device_states.clear()

    # ------------------------------------------------------------------ serve
    def _fill_bucket(self) -> int:
        """Power-of-two serve tile covering the current fill (<= capacity)."""
        return bucket_size(self.n, self.min_fill_bucket, cap=self.capacity)

    def _padded_state(self, bucket: Optional[int] = None):
        """Device-resident buffers padded to a fill bucket, cached per bucket.

        Padding rows carry k = 0 (valid mask), Sigma^{-1} = I and alpha = 0,
        leaving every product untouched; the jitted serve path therefore
        compiles one program per bucket and its cost scales with fill, not
        capacity. Callers may request a larger bucket than the current fill
        (the stacked multi-synopsis dispatch aligns groups on one bucket).
        """
        bucket = self._fill_bucket() if bucket is None else int(bucket)
        state = self._device_states.get(bucket)
        if state is not None:
            return state
        rows = np.asarray(self._order, dtype=np.int64)
        n = len(rows)
        idx = np.concatenate([rows, np.zeros((bucket - n,), np.int64)])
        past = self._put(self._row_batch(idx))
        valid = self._put(np.asarray(np.arange(bucket) < n, np.float64))
        sinv = np.eye(bucket)
        if n:
            sinv[:n, :n] = np.asarray(self._sigma_inv)
        alpha = np.zeros((bucket,))
        alpha[:n] = np.asarray(self._alpha)
        state = (past, valid, self._put(sinv), self._put(alpha))
        self._device_states[bucket] = state
        return state

    def improve(self, new: SnippetBatch, raw: RawAnswer,
                use_kernel: bool = False) -> ImprovedAnswer:
        """Improved answers for a batch of new snippets (Algorithm 2 lines 3-7).

        Drains pending ingest first (the model the paper conditions on is the
        one containing every recorded answer), then serves from the bucketed
        device state. ``use_kernel=True`` routes the fused inference through
        the ``gp_batch_infer`` Pallas kernel (f32 MXU path) instead of the
        jnp f64 program; validation (Appendix B) applies either way.
        """
        self.drain()
        if self.n == 0 or self.quarantined:
            # Empty synopsis (Theorem 1's equality case) or quarantined
            # (degraded mode): return raw unchanged — always a valid,
            # honest answer.
            acc = jnp.zeros((new.n,), bool)
            return ImprovedAnswer(raw.theta, raw.beta2, raw.theta, raw.beta2, acc)
        q = new.n
        qb = bucket_size(q, self.min_q_bucket)
        padded_new = pad_snippets(new, qb)
        raw_theta = _pad_raw(raw.theta, qb, 0.0)
        raw_beta2 = _pad_raw(raw.beta2, qb, 1.0)
        past, valid, sinv, alpha = self._padded_state()
        if use_kernel:
            from repro.kernels.gp_batch_infer import ops as gp_ops

            # The f64 covariance inputs are model math (``model_device``);
            # the kernel and the validation run on the serving device.
            inputs = (past, valid, self.params, padded_new)
            if self._host is not None:
                inputs = jax.device_put(inputs, self._host)
            k_mat, kappa2, mu_new = _improve_inputs_jit(*inputs)
            if self._host is not None:
                k_mat, kappa2, mu_new = jax.device_put(
                    (k_mat, kappa2, mu_new), self.device or jax.devices()[0])
            m_theta, m_beta2, _ = gp_ops.gp_batch_infer(
                k_mat, sinv, alpha, kappa2, mu_new, raw_theta, raw_beta2
            )
            theta, beta2, accepted = validation.validate(
                padded_new.agg, m_theta, m_beta2, raw_theta, raw_beta2,
                self.delta_v,
            )
        else:
            theta, beta2, accepted = _improve_padded(
                past, valid, sinv, alpha, self.params, padded_new,
                raw_theta, raw_beta2, self.delta_v,
            )
        return ImprovedAnswer(
            theta[:q], beta2[:q], raw.theta, raw.beta2, accepted[:q]
        )

    # ------------------------------------------------------------- append (D)
    def apply_append(self, stats):
        """Adjust all stored answers for appended data (Appendix D, Lemma 3)."""
        from repro.core.append import adjust_answers

        self.drain()
        if self.n == 0:
            return
        rows = np.arange(self.n)
        with self._on_host():
            theta, beta2 = adjust_answers(
                jnp.asarray(self._theta[rows]),
                jnp.asarray(self._beta2[rows]),
                jnp.asarray(self._measure[rows]),
                jnp.asarray(self._agg[rows]),
                stats,
            )
        self._theta[rows] = np.asarray(theta)
        self._beta2[rows] = np.asarray(beta2)
        self.rebuild()
        self.generation += 1  # stored answers rescaled for appended data

    # ------------------------------------------------------------ persistence
    def state_dict(self):
        """Host snapshot of the learned state (drains pending ingest first).

        Every array is a copy — never a live view into the ring buffers — so
        snapshots stay valid across later ``add`` calls (checkpointing relies
        on this).

        Raises ``SynopsisQuarantinedError`` while quarantined: a model with
        half-applied batches must never persist (``heal()`` first).
        """
        self.drain()
        if self.quarantined:
            raise SynopsisQuarantinedError(self.name, self._quarantine_exc)
        n = self.n
        return {
            "lo": np.array(self._lo[:n]),
            "hi": np.array(self._hi[:n]),
            "cat": np.array(self._cat[:n]),
            "agg": np.array(self._agg[:n]),
            "measure": np.array(self._measure[:n]),
            "theta": np.array(self._theta[:n]),
            "beta2": np.array(self._beta2[:n]),
            "stamp": np.array(self._stamp[:n]),
            "order": np.asarray(self._order, np.int64),
            "log_ls": np.array(np.asarray(self.params.log_ls)),
            "log_sigma2": np.array(np.asarray(self.params.log_sigma2)),
            "mu": np.array(np.asarray(self.params.mu)),
            "ingest_high_water": np.asarray(self.ingest_high_water, np.int64),
        }

    def load_state_dict(self, state):
        self.drain()
        if "ingest_high_water" in state:  # absent in pre-back-pressure dumps
            self._restored_high_water = int(state["ingest_high_water"])
        n = state["lo"].shape[0]
        self.n = n
        self._lo[:n] = state["lo"]
        self._hi[:n] = state["hi"]
        self._cat[:n] = state["cat"]
        self._agg[:n] = state["agg"]
        self._measure[:n] = state["measure"]
        self._theta[:n] = state["theta"]
        self._beta2[:n] = state["beta2"]
        self._stamp[:n] = state["stamp"]
        self._order = [int(x) for x in state["order"]]
        self.params = GPParams(
            log_ls=jnp.asarray(state["log_ls"]),
            log_sigma2=jnp.asarray(state["log_sigma2"]),
            mu=jnp.asarray(state["mu"]),
        )
        self._keys = {
            self._key(self._lo[i], self._hi[i], self._cat[i], self._agg[i], self._measure[i]): i
            for i in range(n)
        }
        self._clock = int(self._stamp[:n].max()) if n else 0
        self.rebuild()
        self.generation += 1  # restored state ≠ whatever answers were cached
