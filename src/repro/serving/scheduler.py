"""Continuous-batching scheduler (the serving hot path — docs/SERVING.md).

``ContinuousEngine`` keeps a fixed-lane decode batch backed by a slotted KV
cache (``models.transformer.cache_slots_like`` / ``cache_slot_insert`` /
``cache_slot_evict``). Sequences are admitted and evicted mid-flight:

- **submit** queues a request (batch-1 prompt + per-request max_new/eos).
- **step** is one scheduler tick: deficit-driven prefill (chunks of
  ``serve.prefill_chunk`` positions keep running while a decode lane would
  otherwise sit empty, one chunk per tick once supply covers the lanes)
  interleaved with one decode step over every occupied lane. Decode never
  waits for a whole prefill once lanes are fed, so time-to-first-token
  stays bounded under load; finished lanes are reused immediately instead
  of padding the batch to the slowest sequence (the static-batch failure
  mode).

Prefill runs at batch 1 through the incremental engine API
(``engine.prefill_begin/prefill_step/prefill_finish``); on completion the
first token is sampled from the prefill logits and the request's cache is
written into a free lane — the whole lane is overwritten, which is what
makes eviction reuse sound without any cache zeroing.

Greedy decoding is token-identical per sequence to the static
``engine.generate`` baseline (pinned in tests/test_serving.py): every
attention/cache op is row-wise in the batch axis, so lane composition and
per-lane positions don't change a sequence's numerics. Temperature > 0
draws from a per-request key stream (``fold_in(seed, rid)``) and is *not*
bit-matched to the static engine's shared key stream.

EOS convention matches ``engine.generate``: eos itself is never emitted;
``FinishedSeq.tokens`` holds exactly ``steps`` usable tokens.
"""
from __future__ import annotations

import functools
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import Config
from repro.core import faults
from repro.kernels import ops as kops
from repro.models import transformer as T
from repro.serving import engine as E


class QueueFullError(RuntimeError):
    """Raised by :meth:`ContinuousEngine.submit` when the admission queue is
    at ``serve.max_queue`` — explicit rejection beats unbounded memory."""


class FinishedSeq(NamedTuple):
    rid: int
    tokens: np.ndarray      # (steps,) generated ids, eos excluded
    steps: int              # == len(tokens)
    prompt_len: int         # decoder prompt positions (incl. frontend)
    status: str = "ok"      # ok | timeout | quarantined | cancelled | error


class _Pending(NamedTuple):
    rid: int
    batch: Dict[str, jax.Array]
    max_new: int
    eos_id: int
    deadline: float = float("inf")   # absolute clock() time, inf = no limit


def _poison_lane(caches: Any, lane: int) -> Any:
    """``serve.decode_step`` fault payload: NaN-fill one lane of the slotted
    KV cache (lane axis is axis 1 on every leaf — transformer.py). The next
    decode step's logits for that lane go non-finite, which is exactly what
    the quarantine guard detects; all other lanes are untouched."""
    def nanfill(a):
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.at[:, lane].set(jnp.nan)
    return jax.tree_util.tree_map(nanfill, caches)


class _Prefill:
    """A request mid-prefill: embedded inputs + batch-1 caches + cursor."""

    def __init__(self, req: _Pending, h: jax.Array, caches: Any):
        self.req = req
        self.h = h
        self.caches = caches
        self.start = 0
        self.h_last = None
        self.first = None       # first sampled token, set at completion

    @property
    def done(self) -> bool:
        return self.start >= self.h.shape[1]


class StepReport(NamedTuple):
    admitted: List[int]         # rids that began prefill this tick
    prefill_rid: Optional[int]  # rid that ran a prefill chunk this tick
    first_tokens: List[tuple]   # (rid, token) sampled from prefill logits
    decoded: List[tuple]        # (rid, token) decode-step emissions
    finished: List[FinishedSeq]
    active: int                 # occupied decode lanes after this tick
    lanes: int


class ContinuousEngine:
    """Slot-based continuous batching over a fixed decode-lane batch."""

    def __init__(self, cfg: Config, params: Any, *,
                 max_len: Optional[int] = None, seed: int = 0,
                 clock: Optional[Callable[[], float]] = None):
        self.cfg = cfg
        self.params = params
        self.lanes = cfg.serve.max_batch
        self.cap = max_len or cfg.model.max_seq_len
        self.seed = seed
        # deadlines run off an injectable clock so the bench can drive
        # timeouts on its virtual-time axis (benchmarks/serving_bench.py)
        self.clock = clock or time.monotonic
        self._impl = cfg.serve.w4a16_impl
        self._kv_impl = cfg.serve.kv_impl
        self._next_rid = 0
        self._queue: deque = deque()
        self._prefill: Optional[_Prefill] = None
        self._ready: deque = deque()        # prefilled, waiting for a lane
        self._caches: Any = None            # slotted decode cache
        # host-side lane table
        self._lane_rid = np.full((self.lanes,), -1, np.int64)
        self._token = np.zeros((self.lanes,), np.int32)
        self._pos = np.zeros((self.lanes,), np.int32)
        self._remaining = np.zeros((self.lanes,), np.int32)
        self._eos = np.full((self.lanes,), -1, np.int32)
        self._deadline = np.full((self.lanes,), np.inf)
        self._out: Dict[int, List[int]] = {}
        self._prompt_len: Dict[int, int] = {}
        self._nstep: Dict[int, int] = {}
        # failure accounting — every eviction/rejection/degradation is
        # counted, never silent (docs/SERVING.md "Failure handling")
        self.stats: Dict[str, int] = {
            "timeout_evictions": 0, "rejections": 0, "cancelled": 0,
            "quarantined": 0, "kernel_degradations": 0,
            "prefill_failures": 0,
        }
        # per-instance trace-time fallback counters (kernels.ops routes
        # notes to the innermost active scope): two engines in one process
        # must not read each other's downgrades out of the module-global
        self._kernel_fallbacks: Dict[str, int] = {}
        self._build_jit()

    def _build_jit(self) -> None:
        """(Re)build the jitted step functions. Called once at init and
        again after a pallas→xla kernel degradation: the w4a16 backend is
        chosen at trace time, so surviving compiled entries must be dropped
        for the new default to take effect."""
        cfg = self.cfg
        self._jit_decode = jax.jit(functools.partial(E.serve_step, cfg))
        # greedy sampling + finite-logits flag fused into the jitted decode
        # step (one dispatch and two (lanes,) transfers per tick instead of
        # logits + host argmax)
        self._jit_decode_guarded = jax.jit(
            functools.partial(E.decode_step_guarded, cfg))
        self._jit_insert = jax.jit(T.cache_slot_insert)
        # prefill pieces are jitted per shape: begin keys on prompt length,
        # step on (chunk length, start) — a small set, since starts are
        # multiples of serve.prefill_chunk
        self._jit_pf_begin = jax.jit(functools.partial(E.prefill_begin, cfg),
                                     static_argnums=(2,))
        self._jit_pf_step = jax.jit(functools.partial(E.prefill_step, cfg),
                                    static_argnums=(2,))
        self._jit_pf_finish = jax.jit(functools.partial(E.prefill_finish,
                                                        cfg))

    def _guarded(self, name: str, *args):
        """Run one jitted piece under the current kernel backends (w4a16
        matmul + int8-KV attention); on an injected kernel fault, degrade
        pallas→xla (rebuild jits, count, warn) and retry the same call once.
        Already-xla faults, other sites' faults and real errors (a Mosaic
        lowering failure included) propagate."""
        with kops.w4a16_default_impl(self._impl), \
                kops.kv_attn_default_impl(self._kv_impl), \
                kops.fallback_scope(self._kernel_fallbacks):
            try:
                return getattr(self, name)(*args)
            except Exception as e:          # noqa: BLE001 — classified below
                if (self._impl == "xla" and self._kv_impl == "xla") \
                        or not E._kernel_fault(e):
                    raise
                self.stats["kernel_degradations"] += 1
                warnings.warn(
                    f"kernel fault in {name} ({e!r}): degrading "
                    "engine to impl='xla'", RuntimeWarning, stacklevel=2)
        self._impl = "xla"
        self._kv_impl = "xla"
        self._build_jit()
        with kops.w4a16_default_impl("xla"), \
                kops.kv_attn_default_impl("xla"), \
                kops.fallback_scope(self._kernel_fallbacks):
            return getattr(self, name)(*args)

    def engine_stats(self) -> Dict[str, Any]:
        """Failure counters + current kernel backend + trace-time fallback
        counters — the observable surface the bench and tests assert on.
        ``kernel_fallbacks`` is *this instance's* scope (kernels.ops
        fallback_scope), not the process-global dict, so two engines in one
        process never report each other's downgrades."""
        s: Dict[str, Any] = dict(self.stats)
        s["w4a16_impl"] = self._impl
        s["kv_impl"] = self._kv_impl
        s["kernel_fallbacks"] = dict(self._kernel_fallbacks)
        return s

    # -- submission --------------------------------------------------------

    def submit(self, batch: Dict[str, jax.Array], *,
               max_new_tokens: Optional[int] = None,
               eos_id: int = -1,
               timeout_s: Optional[float] = None,
               force: bool = False) -> int:
        """Queue one request. ``batch`` is batch-1 ({tokens, embeds?/frames?}).

        Raises :class:`QueueFullError` (counted in ``stats["rejections"]``)
        when ``serve.max_queue > 0`` and that many requests are already
        waiting for admission. ``timeout_s`` (default
        ``serve.request_timeout_s``; 0 = no deadline) starts the request's
        wall-clock budget now — queue wait counts against it.

        ``force=True`` bypasses the queue bound: the supervisor's crash
        replay resubmits every in-flight request at once — requests that
        were already *admitted* (lanes, ready set, prefill) before the
        crash, so re-rejecting them at the admission bound would turn a
        recovery into silent request loss.
        """
        assert batch["tokens"].shape[0] == 1, "submit one sequence at a time"
        max_queue = self.cfg.serve.max_queue
        if not force and max_queue > 0 and len(self._queue) >= max_queue:
            self.stats["rejections"] += 1
            raise QueueFullError(
                f"admission queue full ({len(self._queue)} >= {max_queue})")
        mnt = max_new_tokens or self.cfg.serve.max_new_tokens
        s0 = batch["tokens"].shape[1]
        n_front = batch["embeds"].shape[1] if batch.get("embeds") is not None \
            else 0
        assert s0 + n_front + mnt + 1 <= self.cap, \
            f"request needs {s0 + n_front + mnt + 1} positions, cap={self.cap}"
        tmo = self.cfg.serve.request_timeout_s if timeout_s is None \
            else timeout_s
        deadline = self.clock() + tmo if tmo and tmo > 0 else float("inf")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(_Pending(rid, batch, mnt, eos_id, deadline))
        return rid

    def cancel(self, rid: int) -> Optional[FinishedSeq]:
        """Cancel a request wherever it is (queued, mid-prefill, parked,
        decoding). Returns a partial :class:`FinishedSeq` with status
        ``"cancelled"`` (tokens produced so far), or None if ``rid`` is not
        in flight."""
        for q in (self._queue, self._ready):
            for item in list(q):
                item_rid = item.rid if isinstance(item, _Pending) \
                    else item.req.rid
                if item_rid == rid:
                    q.remove(item)
                    self.stats["cancelled"] += 1
                    return self._finish_rid(rid, "cancelled")
        if self._prefill is not None and self._prefill.req.rid == rid:
            self._prefill = None
            self.stats["cancelled"] += 1
            return self._finish_rid(rid, "cancelled")
        lanes = np.nonzero(self._lane_rid == rid)[0]
        if lanes.size:
            self._evict(int(lanes[0]))
            self.stats["cancelled"] += 1
            return self._finish_rid(rid, "cancelled")
        return None

    # -- scheduling --------------------------------------------------------

    @property
    def active(self) -> int:
        return int((self._lane_rid >= 0).sum())

    @property
    def idle(self) -> bool:
        return (not self._queue and self._prefill is None
                and not self._ready and self.active == 0)

    def step(self) -> StepReport:
        """One tick: ≤1 prefill chunk + one decode step over active lanes.

        The w4a16 backend context is installed per jitted call inside
        :meth:`_guarded` (not here) so a mid-tick pallas→xla degradation
        takes effect for the retry of the very call that faulted.

        The ``serve.engine_step`` kill site fires *before* any tick
        mutation, modeling the whole engine dying between ticks — the
        supervisor (serving/supervisor.py) catches the escaped exception,
        rebuilds the engine, and replays in-flight requests.
        """
        faults.fire("serve.engine_step")
        return self._step()

    def _sweep_deadlines(self, finished: List[FinishedSeq]) -> None:
        """Evict every request past its deadline — queued, mid-prefill,
        parked, or decoding. The freed lane is refilled by the normal
        admission path in the same tick."""
        now = self.clock()
        for req in [r for r in self._queue if r.deadline < now]:
            self._queue.remove(req)
            self.stats["timeout_evictions"] += 1
            finished.append(self._finish_rid(req.rid, "timeout"))
        if self._prefill is not None and \
                self._prefill.req.deadline < now:
            self.stats["timeout_evictions"] += 1
            finished.append(self._finish_rid(self._prefill.req.rid,
                                             "timeout"))
            self._prefill = None
        for pf in [p for p in self._ready if p.req.deadline < now]:
            self._ready.remove(pf)
            self.stats["timeout_evictions"] += 1
            finished.append(self._finish_rid(pf.req.rid, "timeout"))
        for i in np.nonzero((self._lane_rid >= 0)
                            & (self._deadline < now))[0]:
            rid = int(self._lane_rid[i])
            self._evict(int(i))
            self.stats["timeout_evictions"] += 1
            finished.append(self._finish_rid(rid, "timeout"))

    def _step(self) -> StepReport:
        admitted: List[int] = []
        first_tokens: List[tuple] = []
        finished: List[FinishedSeq] = []
        prefill_rid = None

        self._sweep_deadlines(finished)

        # refill freed lanes from already-prefilled parked requests
        while self._ready and self.active < self.lanes:
            self._insert(self._ready.popleft())

        # admit: prefill runs concurrently even with every lane busy — a
        # prefill completing with no free lane parks in _ready and is
        # inserted the moment an eviction frees one (no refill latency)
        if self._prefill is None and self._queue:
            admitted.append(self._admit())

        # prefill: deficit-driven. While the next decode tick would leave a
        # lane empty (active + parked supply < lanes), keep running chunks —
        # across request boundaries — so prefill throughput tracks lane
        # drain instead of capping at one chunk per tick (which starves
        # lanes under load). Once supply covers every lane, at most one
        # chunk per tick bounds the prefill latency each decode tick pays.
        # chunk 0 == whole prompt at once.
        ran_chunk = False
        while self._prefill is not None:
            pf = self._prefill
            starved = self.active + len(self._ready) < self.lanes
            if ran_chunk and not starved and self.active > 0:
                break
            chunk = self.cfg.serve.prefill_chunk or pf.h.shape[1]
            c0 = pf.start
            c1 = min(pf.h.shape[1], c0 + chunk)
            try:
                faults.fire("serve.prefill_chunk")
                pf.h_last, pf.caches = self._guarded(
                    "_jit_pf_step", self.params, pf.h[:, c0:c1], c0,
                    pf.caches)
            except faults.FaultError as e:
                if e.site != "serve.prefill_chunk":
                    raise
                # a failed prefill drops only its own request — lanes and
                # parked requests are untouched, the slot is re-admitted
                # from the queue immediately
                self.stats["prefill_failures"] += 1
                finished.append(self._finish_rid(pf.req.rid, "error"))
                self._prefill = None
                if self._queue:
                    admitted.append(self._admit())
                continue
            pf.start = c1
            ran_chunk = True
            prefill_rid = pf.req.rid
            if pf.done:
                first_tokens.extend(self._complete_prefill(pf, finished))
                self._prefill = None
                if self._queue:
                    admitted.append(self._admit())

        # one decode step over every occupied lane
        decoded = self._decode_tick(finished) if self.active else []

        return StepReport(admitted, prefill_rid, first_tokens, decoded,
                          finished, self.active, self.lanes)

    def run(self) -> Dict[int, FinishedSeq]:
        """Drain: tick until every submitted request has finished."""
        done: Dict[int, FinishedSeq] = {}
        while not self.idle:
            for f in self.step().finished:
                done[f.rid] = f
        return done

    # -- internals ---------------------------------------------------------

    def _admit(self) -> int:
        req = self._queue.popleft()
        h, caches = self._guarded("_jit_pf_begin", self.params, req.batch,
                                  self.cap)
        self._prefill = _Prefill(req, h, caches)
        self._prompt_len[req.rid] = h.shape[1]
        return req.rid

    def _key(self, rid: int, step: int) -> jax.Array:
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), rid), step)

    def _complete_prefill(self, pf: _Prefill, finished: List[FinishedSeq]
                          ) -> List[tuple]:
        req = pf.req
        logits = self._guarded("_jit_pf_finish", self.params, pf.h_last)
        first = int(E._sample(self._key(req.rid, 0), logits,
                              self.cfg.serve.temperature)[0])
        if first == req.eos_id:        # eos on the very first sample
            finished.append(FinishedSeq(req.rid, np.zeros((0,), np.int32), 0,
                                        self._prompt_len.pop(req.rid, 0)))
            return []
        self._out[req.rid] = [first]
        self._nstep[req.rid] = 1
        if req.max_new <= 1:
            finished.append(self._finish_rid(req.rid))
            return [(req.rid, first)]
        pf.first = first
        if self.active < self.lanes:
            self._insert(pf)
        else:
            self._ready.append(pf)
        return [(req.rid, first)]

    def _insert(self, pf: _Prefill) -> None:
        req = pf.req
        lane = int(np.nonzero(self._lane_rid < 0)[0][0])
        if self._caches is None:
            self._caches = T.cache_slots_like(pf.caches, self.lanes)
        self._caches = self._guarded("_jit_insert", self._caches, pf.caches,
                                     jnp.int32(lane))
        self._lane_rid[lane] = req.rid
        self._token[lane] = pf.first
        self._pos[lane] = self._prompt_len[req.rid]
        self._remaining[lane] = req.max_new - 1
        self._eos[lane] = req.eos_id
        self._deadline[lane] = req.deadline

    def _finish_rid(self, rid: int, status: str = "ok") -> FinishedSeq:
        toks = np.asarray(self._out.pop(rid, []), np.int32)
        return FinishedSeq(rid, toks, self._nstep.pop(rid, 0),
                           self._prompt_len.pop(rid, 0), status)

    def _decode_tick(self, finished: List[FinishedSeq]) -> List[tuple]:
        temp = self.cfg.serve.temperature
        decoded: List[tuple] = []
        # serve.decode_step fault: poison the first occupied lane's KV cache
        # before the dispatch — the SAME fused step that decodes every lane
        # detects it via the finite-logits flags (no separate checking path
        # to keep honest)
        fspec = faults.poll("serve.decode_step")
        if fspec is not None:
            lane = int(np.nonzero(self._lane_rid >= 0)[0][0])
            self._caches = _poison_lane(self._caches, lane)
        if temp <= 0.0:
            raw_dev, ok_dev, self._caches = self._guarded(
                "_jit_decode_guarded", self.params, jnp.asarray(self._token),
                jnp.asarray(self._pos), self._caches)
            raw = np.asarray(raw_dev)
            ok = np.asarray(ok_dev)
        else:
            logits, self._caches = self._guarded(
                "_jit_decode", self.params, jnp.asarray(self._token),
                jnp.asarray(self._pos), self._caches)
            ok = np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
            raw = np.array([
                int(E._sample(self._key(int(self._lane_rid[i]),
                                        self._nstep.get(
                                            int(self._lane_rid[i]), 0)),
                              logits[i:i + 1], temp)[0])
                if self._lane_rid[i] >= 0 else 0
                for i in range(self.lanes)], np.int32)
        nan_guard = self.cfg.serve.decode_nan_guard
        for i in np.nonzero(self._lane_rid >= 0)[0]:
            rid = int(self._lane_rid[i])
            if nan_guard and not ok[i]:
                # quarantine: evict only the poisoned lane; its slot is
                # overwritten wholesale on the next admission, and every
                # other lane's numerics are row-wise independent of it
                self._evict(int(i))
                self.stats["quarantined"] += 1
                finished.append(self._finish_rid(rid, "quarantined"))
                continue
            tok = int(raw[i])
            if tok == self._eos[i]:
                self._evict(int(i))
                finished.append(self._finish_rid(rid))
                continue
            self._out[rid].append(tok)
            self._nstep[rid] += 1
            decoded.append((rid, tok))
            self._token[i] = tok
            self._pos[i] += 1
            self._remaining[i] -= 1
            if self._remaining[i] == 0:
                self._evict(int(i))
                finished.append(self._finish_rid(rid))
        return decoded

    def _evict(self, lane: int) -> None:
        # bookkeeping only: cache_slot_insert overwrites the whole lane on
        # the next admission, so zeroing the cache here (cache_slot_evict)
        # would be a pure extra dispatch on the hot path
        self._lane_rid[lane] = -1
        self._token[lane] = 0
        self._pos[lane] = 0
        self._remaining[lane] = 0
        self._eos[lane] = -1
        self._deadline[lane] = np.inf
