"""Declarative quantization plan: group same-shape linears, execute batched.

The pipeline's capture pass produces one :class:`PlanMember` per linear
(dense taps and stacked MoE expert slices alike). :func:`build_plan` groups
members by ``(out, in, n_last, group_size, blocksize, bits, symmetric)`` —
everything that determines a jit cache entry — and :func:`execute_plan`
hands each group to the **batched executors**
(:func:`repro.core.gptq.gptq_quantize_batched`,
:func:`repro.core.rpiq.rpiq_refine_batched`): the group's weights,
Hessians, grids and last-instance activations are stacked on a leading
axis and quantized in ONE dispatch per stage instead of one per linear.

Why this matters: the paper's headline claim is quantization *throughput*
(single-instance calibration exists to make 4-bit compression cheap on
assistive devices). A transformer layer typically holds ≥4 identically
shaped linears (q/k/v/o) and an MoE layer holds E× identically shaped
expert slices; per-linear dispatch pays trace/dispatch overhead B times
and leaves the accelerator underfilled at small widths. Grouping makes the
cost one compile + one dispatch per *shape class*, with every inner op B×
wider.

MoE starved experts (fewer routed tokens than one quant group) stay inside
their group as a **mask**: the batched RTN fallback is computed for the
whole stack (row-wise, nearly free) and selected per member with
``jnp.where`` — no per-expert Python loop. Members whose input dim doesn't
align to the grid are carried on a per-member fallback list (skip, or
full-row RTN for starved experts), exactly the legacy semantics.

``execute_plan(..., batched=False)`` runs the same plan through the
singleton executors (one dispatch per linear) — the pre-plan reference
path kept for parity tests and the table4 per-linear-vs-batched benchmark.

**Sharded group execution** (``execute_plan(..., mesh=...)``, DESIGN.md
§2.6): each group's stacked slab is embarrassingly parallel over lanes AND
over Cout, so with a ``(data, model)`` mesh the executor lays the slab out
lane-axis over ``data`` and row tiles over ``model``
(:func:`repro.distributed.sharding.quant_group_sharding`), places the
stacked Hessian state lane-local (damp + Cholesky run where their rows
run), and sweeps via ``kernels.ops.gptq_block_sharded`` — one device-local
(member, Cout-tile) kernel per shard, zero sweep collectives.  Groups that
fail the divisibility guards keep the single-device batched path, so every
config stays lowerable; executor cache entries are additionally keyed by
mesh + resolved sharding.  ``quant.mesh`` plumbs this from configs
(launch/mesh.py; docs/QUANTIZATION.md walks the knobs).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import QuantConfig
from repro.core import faults
from repro.core import hessian as hess
from repro.core import spans
from repro.core.gptq import (GPTQResult, gptq_quantize,
                             gptq_quantize_batched, rtn_quantize,
                             rtn_quantize_batched)
from repro.core.rpiq import RPIQResult, rpiq_refine, rpiq_refine_batched
from repro.distributed.sharding import (QuantGroupSharding,
                                        quant_group_sharding)
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# Report records (schema consumed by benchmarks/tables — do not change)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LinearRecord:
    name: str
    shape: Tuple[int, int]           # (out, in)
    gptq_err: float
    gamma: List[float]               # Γ trajectory (Γ[0] = post-stage-1)
    gamma_final: float
    iters: int
    mode: str                        # "rpiq" | "gptq" | "rtn-fallback" |
    #                                  "rtn-guardrail" | "skipped"


@dataclasses.dataclass
class QuantReport:
    linears: List[LinearRecord] = dataclasses.field(default_factory=list)
    seconds_total: float = 0.0
    seconds_stage1: float = 0.0
    seconds_stage2: float = 0.0
    # stream-scheduler telemetry (core/stream.py): wall seconds per
    # layer-step (the overlap schedule's only sync point is the step's
    # report boundary, so this is its per-layer measurement; under serial
    # seconds_stage1/2 stay the synchronized per-stage split) and the
    # {mode, steps, spec_captures, repairs, serial_fallbacks} counters.
    layer_step_seconds: List[float] = dataclasses.field(default_factory=list)
    pipeline_stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # robustness telemetry (additive; empty = nothing triggered): guardrail
    # ladder outcomes per run ({damp_retries, lanes_flagged,
    # lanes_damp_recovered, lanes_rtn_forced}) and the kernels/ops
    # auto→xla fallback counters observed during the run
    guardrail_stats: Dict[str, int] = dataclasses.field(default_factory=dict)
    kernel_fallbacks: Dict[str, int] = dataclasses.field(default_factory=dict)
    # calibration-coverage honesty: per-MoE-layer count of (token, k)
    # assignments dropped by expert capacity during Hessian capture —
    # these tokens never reach any per-expert Hessian (models/moe.py
    # ``_capacity``), so a nonzero entry means that layer's calibration
    # saw fewer instances than the batch implies
    moe_capacity_dropped: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # sharded group execution: groups counted by how many distinct shards
    # each stage's output held on the mesh before the gather
    # ({"stage1_shards=4": n, ...}) — shows the work really spread
    mesh_spread: Dict[str, int] = dataclasses.field(default_factory=dict)
    # host spans of a run traced under jax.profiler, (name, start_ns,
    # end_ns) on time.perf_counter_ns (core/spans.py); empty untraced
    spans: List[Tuple[str, int, int]] = dataclasses.field(
        default_factory=list)

    def summary(self) -> str:
        n = len(self.linears)
        improved = sum(1 for l in self.linears
                       if l.gamma and l.gamma_final < l.gamma[0] * 0.999)
        return (f"{n} linears quantized; stage2 improved {improved}; "
                f"t={self.seconds_total:.1f}s "
                f"(s1={self.seconds_stage1:.1f} s2={self.seconds_stage2:.1f})")


# ---------------------------------------------------------------------------
# Plan structure
# ---------------------------------------------------------------------------

def _n_shards(a: jax.Array) -> int:
    """Distinct index slices of ``a`` over its devices (replicas count
    once)."""
    idx = a.sharding.devices_indices_map(a.shape).values()
    return len({tuple((s.start, s.stop) for s in i) for i in idx})


GroupKey = Tuple[int, int, int, int, int, int, bool]
# (out, in, n_last, group_size, blocksize, bits, symmetric)


@dataclasses.dataclass
class PlanMember:
    """One linear — or a pre-stacked slab of S same-shape linears.

    Singleton (``names is None``): w_oi (out, in), hessian (in, in),
    x_last (n, in), x_count scalar, starved bool.

    Stacked (``names`` lists the S per-slice report names, e.g. one per
    MoE expert): w_oi (S, out, in), hessian (S, in, in)/(S,), x_last
    (S, n, in), x_count (S,), starved bool or (S,) mask. Stacked members
    flow capture → plan → executor → scatter as whole arrays — no
    per-expert device slicing anywhere on the batched path.
    """
    name: str
    w_oi: jax.Array                  # (out, in) | (S, out, in) float32
    hessian: hess.HessianState       # (in, in) | stacked (S, in, in)
    x_last: jax.Array                # (n, in) | (S, n, in) inputs
    x_count: Optional[jax.Array]     # () | (S,) int32 real rows in x_last
    #                                  (None ⇒ all n rows are real)
    starved: Any = False             # bool | (S,) mask: below one quant
    #                                  group of tokens → RTN fallback
    names: Optional[List[str]] = None  # per-slice names when stacked

    @property
    def stacked(self) -> bool:
        return self.names is not None

    @property
    def lanes(self) -> int:
        return len(self.names) if self.stacked else 1

    @property
    def lane_names(self) -> List[str]:
        return self.names if self.stacked else [self.name]

    @property
    def wshape(self) -> Tuple[int, int]:
        return tuple(self.w_oi.shape[-2:])

    def starved_mask(self) -> np.ndarray:
        s = np.asarray(self.starved, bool).reshape(-1)
        return np.full(self.lanes, bool(s[0])) if s.size == 1 else s


@dataclasses.dataclass
class QuantGroup:
    key: GroupKey
    members: List[PlanMember]


@dataclasses.dataclass
class QuantPlan:
    groups: List[QuantGroup]         # batched-executable, grid-aligned
    fallbacks: List[PlanMember]      # in % group/blocksize ≠ 0: skip or
    #                                  full-row RTN (starved)

    @property
    def n_members(self) -> int:
        return sum(len(g.members) for g in self.groups) + len(self.fallbacks)


@dataclasses.dataclass
class MemberResult:
    """Per-member outcome, keyed back to the param tree by ``name``.

    Stacked members return stacked arrays: w_q (S, out, in) and grid
    (S, out, groups) — the scatter assigns them wholesale.
    """
    name: str
    w_q: Optional[jax.Array]         # (out, in)|(S, out, in); None = skipped
    grid: Optional[Tuple[jax.Array, jax.Array]]   # stage-1 (scales, zeros)


def build_plan(qc: QuantConfig, members: List[PlanMember]) -> QuantPlan:
    """Group members by jit-cache identity; order inside a group is the
    member submission order (stable), so scatter-back is positional."""
    groups: Dict[GroupKey, List[PlanMember]] = {}
    fallbacks: List[PlanMember] = []
    for m in members:
        out_dim, in_dim = m.wshape
        if in_dim % qc.blocksize != 0 or in_dim % qc.group_size != 0:
            fallbacks.append(m)
            continue
        key: GroupKey = (out_dim, in_dim, int(m.x_last.shape[-2]),
                         qc.group_size, qc.blocksize, qc.bits, qc.symmetric)
        groups.setdefault(key, []).append(m)
    return QuantPlan([QuantGroup(k, v) for k, v in groups.items()],
                     fallbacks)


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------

def _gamma_list(hist_row: np.ndarray) -> List[float]:
    return [float(g) for g in hist_row if np.isfinite(g)]


def _as3d(a: jax.Array) -> jax.Array:
    return a if a.ndim == 3 else a[None]


def _lane_x_counts(m: PlanMember) -> jax.Array:
    """(S,) int32 real-row counts; starved lanes report n (see below)."""
    n = m.x_last.shape[-2]
    if m.x_count is None:
        xc = jnp.full((m.lanes,), n, jnp.int32)
    else:
        xc = jnp.asarray(m.x_count, jnp.int32).reshape(-1)
        if xc.shape[0] != m.lanes:
            xc = jnp.broadcast_to(xc, (m.lanes,))
    # starved lanes pair with the identity curvature below: x_count = n
    # keeps the eq.-13 rescale at 1 instead of zeroing it
    return jnp.where(jnp.asarray(m.starved_mask()), n, xc)


def _lane_hessians(m: PlanMember) -> hess.HessianState:
    """(S, in, in) curvature block fed to the batched lanes.

    Starved lanes are masked to RTN afterwards, but they still *execute*
    GPTQ/RPIQ under vmap; a zero-token expert has H = 0 and x_count = 0,
    whose Cholesky is NaN — and a NaN Γ never satisfies the early-stop
    predicate, pinning the whole group's while_loop at t_max. Feed those
    lanes an identity Hessian (count = n) so they converge immediately;
    the mask discards their output either way.
    """
    H = _as3d(m.hessian.H)
    count = jnp.asarray(m.hessian.count, jnp.int32).reshape(-1)
    sv = m.starved_mask()
    if sv.any():
        svj = jnp.asarray(sv)
        n = m.x_last.shape[-2]
        eye = jnp.eye(H.shape[-1], dtype=jnp.float32)
        H = jnp.where(svj[:, None, None], eye, H)
        count = jnp.where(svj, n, count)
    return hess.HessianState(H, count)


# ---------------------------------------------------------------------------
# Cross-layer executor jit cache
#
# Sequential calibration walks the stack layer by layer, but the executor
# entry a group needs is fully determined by its signature — GroupKey plus
# the stage statics, the sweep backend, and (when sharded) the mesh + the
# resolved group sharding.  Keying the jitted stage closures in a
# module-level cache means the q/k/v/o group of layer 7 reuses the entry
# layer 0 compiled (first half of the ROADMAP "cross-layer plan batching"
# item; the pipelined-capture half remains open).  Each cached entry
# binds its stage's statics: stage 1 is two jits, damp + Cholesky and then
# the GPTQ sweep (+ the RTN fallback lane when the group has starved
# members), stage 2 wraps the RPIQ refinement.  Sharded stage-1 entries
# close over the mesh (the sweep goes through gptq_block_sharded's
# shard_map), so the mesh component of the key is what keeps
# single-device and sharded entries — or two different meshes — from
# aliasing.
# ---------------------------------------------------------------------------

_EXEC_CACHE: Dict[Tuple, Callable] = {}
_EXEC_CACHE_STATS = {"hits": 0, "misses": 0}
_EXEC_CACHE_MAX = 64     # FIFO-evict beyond this: entries hold compiled
#                          executables, and jax.clear_caches() doesn't see
#                          them — a long-lived process sweeping shapes/
#                          configs must not accumulate programs unboundedly


def executor_cache_stats() -> Dict[str, int]:
    """Copy of {hits, misses} for the cross-layer executor cache."""
    return dict(_EXEC_CACHE_STATS)


def clear_executor_cache() -> None:
    _EXEC_CACHE.clear()
    _EXEC_CACHE_STATS["hits"] = 0
    _EXEC_CACHE_STATS["misses"] = 0


def _cached_executor(key: Tuple, make: Callable[[], Callable]) -> Callable:
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        _EXEC_CACHE_STATS["misses"] += 1
        while len(_EXEC_CACHE) >= _EXEC_CACHE_MAX:
            _EXEC_CACHE.pop(next(iter(_EXEC_CACHE)))
        fn = make()
        _EXEC_CACHE[key] = fn
    else:
        _EXEC_CACHE_STATS["hits"] += 1
    return fn


_GUARDRAIL_KEYS = ("damp_retries", "lanes_flagged", "lanes_damp_recovered",
                   "lanes_rtn_forced")


def _guardrail_stats(report: QuantReport) -> Dict[str, int]:
    for k in _GUARDRAIL_KEYS:
        report.guardrail_stats.setdefault(k, 0)
    return report.guardrail_stats


def _finite_lanes(res1: GPTQResult) -> np.ndarray:
    """(B,) host mask: lane produced fully finite stage-1 outputs.

    One fused reduction per array — any NaN/Inf (a failed Cholesky turns
    the whole lane NaN) poisons the lane's sum. This is the guardrail
    ladder's detector, so it synchronizes on stage 1; the transfer is B
    floats.
    """
    tot = (jnp.sum(res1.w_q, axis=(-2, -1)) +
           jnp.sum(res1.scales, axis=(-2, -1)) +
           jnp.sum(res1.zeros, axis=(-2, -1)))
    return np.asarray(jnp.isfinite(tot + res1.err))


def _make_stage1(qc: QuantConfig, impl: str, with_rtn: bool,
                 gshard: Optional[QuantGroupSharding] = None) -> Callable:
    bits, group_size = qc.bits, qc.group_size
    blocksize, symmetric = qc.blocksize, qc.symmetric

    # damp + Cholesky are a program of their own, the same with or without
    # a mesh: XLA rounds the factor differently with what else the program
    # holds (a v5e fusing it with the in=3072 sweep flips 15 % of the codes
    # against a standalone factor), so a factor fused into the sharded
    # sweep's program would part from the unsharded one.
    @jax.jit
    def factor(H, percdamp):
        # H arrives committed to the group sharding (lane-local), so each
        # lane factors where its rows live.
        hd = hess.damped(hess.HessianState(H, None), percdamp)
        return hd, hess.cholesky_inverse_upper(hd)

    @jax.jit
    def sweep(w, u):
        if gshard is None:
            res1 = gptq_quantize_batched(w, u, bits=bits,
                                         group_size=group_size,
                                         blocksize=blocksize,
                                         symmetric=symmetric, impl=impl)
        else:
            res1 = GPTQResult(*kops.gptq_block_sharded(
                w, u, mesh=gshard.mesh, lane_axis=gshard.lane_axis,
                row_axis=gshard.row_axis, bits=bits, group_size=group_size,
                blocksize=blocksize, symmetric=symmetric, impl=impl))
        rtn = rtn_quantize_batched(w, bits=bits, group_size=group_size,
                                   symmetric=symmetric) if with_rtn else None
        return res1, rtn

    def fn(w, H, percdamp):
        hd, u = factor(H, percdamp)
        return (hd, *sweep(w, u))

    return fn


def _make_stage2(qc: QuantConfig, impl: str,
                 gshard: Optional[QuantGroupSharding] = None) -> Callable:
    kw = dict(bits=qc.bits, group_size=qc.group_size,
              block_size=qc.blocksize, alpha=qc.rpiq_alpha,
              t_max=qc.rpiq_iters, early_stop=qc.rpiq_early_stop,
              symmetric=qc.symmetric,
              exact_gram=not qc.rpiq_use_global_hessian)
    # a named function, so the device trace names the program jit_stage2
    def stage2(w_init, w_fp, x, hd, scales, zeros, h_count=None,
               x_count=None):
        if gshard is None:
            return rpiq_refine_batched(w_init, w_fp, x, hd, scales, zeros,
                                       h_count=h_count, x_count=x_count,
                                       impl=impl, **kw)
        # the stage-2 shard_map twin: lanes shard like stage 1; rows shard
        # only when the per-shard dispatch resolves to the fused kernel
        # (the closed-loop bookkeeping is global over rows — see
        # kernels/ops.rpiq_block_sharded)
        return RPIQResult(*kops.rpiq_block_sharded(
            w_init, w_fp, x, hd, scales, zeros, h_count=h_count,
            x_count=x_count, mesh=gshard.mesh, lane_axis=gshard.lane_axis,
            row_axis=gshard.row_axis, impl=impl, **kw))

    return jax.jit(stage2)


def _execute_group_batched(qc: QuantConfig, group: QuantGroup,
                           report: QuantReport, rpiq_enabled: bool,
                           gshard: Optional[QuantGroupSharding] = None,
                           sync: bool = True,
                           deferred: Optional[List[Callable[[], None]]]
                           = None) -> List[MemberResult]:
    """One stacked dispatch per stage for the whole group.

    Members concatenate on the lane axis — a stacked member (e.g. E MoE
    experts) contributes its slab wholesale, so lane count is
    Σ member.lanes while the host-side work stays O(#members).  Stage
    entries come from the cross-layer cache above, so identically shaped
    groups anywhere in the stack share one compiled executor.

    With ``gshard`` the stacked inputs are committed to the group's mesh
    placement first (weights (lane, row)-tiled, Hessian state and
    instances lane-local) and the stage entries are the mesh-keyed sharded
    variants; the outputs come back sharded and are gathered to the
    default device before scatter (see the comment below — the propagate
    forward must stay single-device).

    ``sync=False`` (the overlap schedule) skips the per-stage
    ``block_until_ready`` so stage dispatches stay async — the stage
    seconds then measure dispatch, and the scheduler takes wall-clock per
    layer-step at its report boundary instead. With ``deferred`` the
    per-linear report records (whose ``np.asarray`` calls would
    synchronize on the executor outputs) are packaged as a closure
    appended to the list, to be materialized at that same boundary —
    record ORDER is preserved, so reports match the serial schedule
    exactly.
    """
    ms = group.members
    t0 = time.perf_counter()
    with spans.span(spans.STAGE1_INPUTS):
        w = jnp.concatenate([_as3d(jnp.asarray(m.w_oi, jnp.float32))
                             for m in ms])
        hs_lanes = [_lane_hessians(m) for m in ms]
        st = hess.HessianState(jnp.concatenate([h.H for h in hs_lanes]),
                               jnp.concatenate([h.count for h in hs_lanes]))
        starved = np.concatenate([m.starved_mask() for m in ms])
        with_rtn = bool(starved.any())
        fspec = faults.poll("hessian.cholesky")
        if fspec is not None:
            st = hess.HessianState(
                hess.corrupt_stacked(st.H, fspec.mode, qc.percdamp),
                st.count)
        shard_key = None if gshard is None else gshard.cache_key()
        if gshard is not None:
            w = jax.device_put(w, gshard.sharding("w"))
            st = hess.shard_stacked(st, gshard)
        stage1 = _cached_executor(
            ("stage1", group.key, qc.gptq_impl, with_rtn, shard_key),
            lambda: _make_stage1(qc, qc.gptq_impl, with_rtn, gshard))
        faults.fire("plan.stage1_executor")
        lanes_total = int(w.shape[0])
        damp = jnp.full((lanes_total,), qc.percdamp, jnp.float32)
    with spans.span(spans.STAGE1):
        hd, res1, rtn, guarded = _stage1_guarded(qc, stage1, w, st.H, damp,
                                                 report)
        if sync:
            jax.block_until_ready(res1.w_q)
    t1 = time.perf_counter()
    report.seconds_stage1 += t1 - t0

    do_rpiq = rpiq_enabled and qc.rpiq_iters > 0
    res2 = None
    if do_rpiq:
        with spans.span(spans.STAGE2_INPUTS):
            x = jnp.concatenate([_as3d(jnp.asarray(m.x_last, jnp.float32))
                                 for m in ms])
            xc = jnp.concatenate([_lane_x_counts(m) for m in ms])
            if gshard is not None:
                # commit the instance batch lane-local so the stage-2
                # shard_map twin (rpiq_block_sharded) keeps each lane's
                # refinement where its rows run without a gather at dispatch
                x = jax.device_put(x, gshard.sharding("x"))
                xc = jax.device_put(xc, gshard.sharding("lane"))
            stage2 = _cached_executor(
                ("stage2", group.key, qc.rpiq_alpha, qc.rpiq_iters,
                 qc.rpiq_early_stop, qc.rpiq_use_global_hessian,
                 qc.rpiq_impl, shard_key),
                lambda: _make_stage2(qc, qc.rpiq_impl, gshard))
            faults.fire("plan.stage2_executor")
        with spans.span(spans.STAGE2):
            res2 = stage2(res1.w_q, w, x, hd, res1.scales, res1.zeros,
                          h_count=st.count, x_count=xc)
            if sync:
                jax.block_until_ready(res2.w_q)
        t2 = time.perf_counter()
        report.seconds_stage2 += t2 - t1

    with spans.span(spans.RESULTS):
        return _group_results(ms, report, res1, res2, rtn, starved, guarded,
                              gshard, deferred)


def _stage1_guarded(qc: QuantConfig, stage1: Callable, w: jax.Array,
                    H: jax.Array, damp: jax.Array, report: QuantReport):
    """Stage 1 under the guardrail ladder; returns (hd, res1, rtn,
    guarded), ``guarded`` the (B,) host mask of lanes forced to RTN."""
    hd, res1, rtn = stage1(w, H, damp)
    guarded = np.zeros(int(w.shape[0]), bool)
    if not qc.guardrail:
        return hd, res1, rtn, guarded
    bad0 = bad = ~_finite_lanes(res1)
    rung = 0
    while bad.any() and rung < qc.guardrail_retries:
        # guardrail ladder rung: escalate damping only on lanes whose
        # stage-1 output went non-finite (non-PSD / NaN Hessian).
        # Every stage-1 op is lane-independent, so untouched lanes
        # reproduce bitwise and the retry reuses the cached executor.
        rung += 1
        _guardrail_stats(report)["damp_retries"] += 1
        damp = jnp.where(jnp.asarray(bad),
                         damp * jnp.float32(qc.guardrail_damp_factor),
                         damp)
        hd, res1, rtn = stage1(w, H, damp)
        bad = ~_finite_lanes(res1)
    if bad0.any():
        gs = _guardrail_stats(report)
        gs["lanes_flagged"] += int(bad0.sum())
        gs["lanes_damp_recovered"] += int((bad0 & ~bad).sum())
        gs["lanes_rtn_forced"] += int(bad.sum())
    if bad.any():
        # ladder exhausted → per-group RTN rung. Stage 2 still runs
        # these lanes under vmap, so feed it sanitized inputs (RTN
        # weights on the RTN grid, identity curvature): a NaN Γ never
        # satisfies the early-stop predicate and would pin the whole
        # group's while_loop at t_max. The mask in _group_results
        # discards their stage-2 output anyway.
        guarded = np.asarray(bad)
        if rtn is None:
            rtn = rtn_quantize_batched(w, bits=qc.bits,
                                       group_size=qc.group_size,
                                       symmetric=qc.symmetric)
        gj = jnp.asarray(guarded)
        sel3 = gj[:, None, None]
        hd = jnp.where(sel3, jnp.eye(hd.shape[-1], dtype=hd.dtype), hd)
        res1 = GPTQResult(jnp.where(sel3, rtn.w_q, res1.w_q),
                          jnp.where(sel3, rtn.scales, res1.scales),
                          jnp.where(sel3, rtn.zeros, res1.zeros),
                          jnp.where(gj, 0.0, res1.err))
    return hd, res1, rtn, guarded


def _group_results(ms: List[PlanMember], report: QuantReport,
                   res1: GPTQResult, res2: Optional[RPIQResult], rtn,
                   starved: np.ndarray, guarded: np.ndarray,
                   gshard: Optional[QuantGroupSharding],
                   deferred: Optional[List[Callable[[], None]]]
                   ) -> List[MemberResult]:
    """A group's outcome: the RTN mask, the gather off the mesh, the
    report records (now, or queued into ``deferred``) and the per-member
    slices of the stacked results."""
    do_rpiq = res2 is not None
    # starved-expert + guardrail-forced mask: select the RTN lane
    # (weights AND grid)
    w_final = res2.w_q if do_rpiq else res1.w_q
    scales, zeros = res1.scales, res1.zeros
    if rtn is not None:
        sel = jnp.asarray(starved | guarded)[:, None, None]
        w_final = jnp.where(sel, rtn.w_q, w_final)
        scales = jnp.where(sel, rtn.scales, scales)
        zeros = jnp.where(sel, rtn.zeros, zeros)

    if gshard is not None:
        for stage, out in (("stage1", res1.w_q),
                           ("stage2", res2.w_q if do_rpiq else None)):
            if out is not None:
                key = f"{stage}_shards={_n_shards(out)}"
                report.mesh_spread[key] = report.mesh_spread.get(key, 0) + 1
        # gather the group's artifacts off the mesh: the scatter feeds the
        # (single-device) propagate forward, and leaving mesh-committed
        # leaves in the param tree would silently partition that forward —
        # perturbing downstream Hessians and breaking parity with the
        # single-device path. The mesh is an executor-internal resource.
        # device_put to one device reshards on-fabric (no host round-trip).
        dev0 = jax.local_devices()[0]
        w_final, scales, zeros = (jax.device_put(a, dev0)
                                  for a in (w_final, scales, zeros))

    def _record():
        # np.asarray synchronizes on the executor outputs — under the
        # overlap schedule this runs deferred, at the step's report
        # boundary, so the dispatch queue has already been refilled.
        err1 = np.asarray(res1.err)
        hist = np.asarray(res2.loss_history) if do_rpiq else None
        ploss = np.asarray(res2.proj_loss) if do_rpiq else None
        iters = np.asarray(res2.iters_run) if do_rpiq else None
        off = 0
        for m in ms:
            shape = m.wshape
            for li, lname in enumerate(m.lane_names):
                i = off + li
                if starved[i]:
                    report.linears.append(LinearRecord(
                        lname, shape, 0.0, [], 0.0, 0, "rtn-fallback"))
                elif guarded[i]:
                    report.linears.append(LinearRecord(
                        lname, shape, 0.0, [], 0.0, 0, "rtn-guardrail"))
                elif do_rpiq:
                    report.linears.append(LinearRecord(
                        lname, shape, float(err1[i]), _gamma_list(hist[i]),
                        float(ploss[i]), int(iters[i]), "rpiq"))
                else:
                    report.linears.append(LinearRecord(
                        lname, shape, float(err1[i]), [], 0.0, 0, "gptq"))
            off += m.lanes

    if deferred is None:
        _record()
    else:
        deferred.append(_record)

    results = []
    off = 0
    for m in ms:
        sl = slice(off, off + m.lanes)
        if m.stacked:
            results.append(MemberResult(m.name, w_final[sl],
                                        (scales[sl], zeros[sl])))
        else:
            results.append(MemberResult(m.name, w_final[off],
                                        (scales[off], zeros[off])))
        off += m.lanes
    return results


def _lane_view(m: PlanMember, li: int) -> "PlanMember":
    """Singleton view of one lane of a stacked member (legacy path only)."""
    if not m.stacked:
        return m
    xc = None if m.x_count is None else \
        jnp.asarray(m.x_count, jnp.int32).reshape(-1)[li]
    return PlanMember(m.lane_names[li], m.w_oi[li],
                      hess.HessianState(m.hessian.H[li],
                                        jnp.asarray(m.hessian.count,
                                                    jnp.int32
                                                    ).reshape(-1)[li]),
                      m.x_last[li], x_count=xc,
                      starved=bool(m.starved_mask()[li]))


def _execute_member_singleton(qc: QuantConfig, m: PlanMember,
                              report: QuantReport, rpiq_enabled: bool
                              ) -> MemberResult:
    """Legacy per-linear path: one dispatch per lane, per stage."""
    if m.stacked:
        parts = [_execute_member_singleton(qc, _lane_view(m, li), report,
                                           rpiq_enabled)
                 for li in range(m.lanes)]
        return MemberResult(m.name,
                            jnp.stack([p.w_q for p in parts]),
                            (jnp.stack([p.grid[0] for p in parts]),
                             jnp.stack([p.grid[1] for p in parts])))
    shape = m.wshape
    if m.starved:
        res = rtn_quantize(jnp.asarray(m.w_oi, jnp.float32), bits=qc.bits,
                           group_size=qc.group_size, symmetric=qc.symmetric)
        report.linears.append(LinearRecord(
            m.name, shape, 0.0, [], 0.0, 0, "rtn-fallback"))
        return MemberResult(m.name, res.w_q, (res.scales, res.zeros))
    t0 = time.perf_counter()
    with spans.span(spans.STAGE1):
        w_oi = jnp.asarray(m.w_oi, jnp.float32)
        hd = hess.damped(m.hessian, qc.percdamp)
        u = hess.cholesky_inverse_upper(hd)
        res1 = gptq_quantize(w_oi, u, bits=qc.bits, group_size=qc.group_size,
                             blocksize=qc.blocksize, symmetric=qc.symmetric,
                             impl=qc.gptq_impl)
        jax.block_until_ready(res1.w_q)
    t1 = time.perf_counter()
    report.seconds_stage1 += t1 - t0
    grid = (res1.scales, res1.zeros)
    if not rpiq_enabled or qc.rpiq_iters <= 0:
        report.linears.append(LinearRecord(
            m.name, shape, float(res1.err), [], 0.0, 0, "gptq"))
        return MemberResult(m.name, res1.w_q, grid)
    with spans.span(spans.STAGE2):
        res2 = rpiq_refine(res1.w_q, w_oi,
                           jnp.asarray(m.x_last, jnp.float32), hd,
                           res1.scales, res1.zeros,
                           h_count=m.hessian.count, x_count=m.x_count,
                           bits=qc.bits, group_size=qc.group_size,
                           block_size=qc.blocksize, alpha=qc.rpiq_alpha,
                           t_max=qc.rpiq_iters,
                           early_stop=qc.rpiq_early_stop,
                           exact_gram=not qc.rpiq_use_global_hessian,
                           symmetric=qc.symmetric, impl=qc.rpiq_impl)
        jax.block_until_ready(res2.w_q)
    t2 = time.perf_counter()
    report.seconds_stage2 += t2 - t1
    report.linears.append(LinearRecord(
        m.name, shape, float(res1.err), _gamma_list(np.asarray(
            res2.loss_history)), float(res2.proj_loss),
        int(res2.iters_run), "rpiq"))
    return MemberResult(m.name, res2.w_q, grid)


def _execute_fallback(qc: QuantConfig, m: PlanMember, report: QuantReport,
                      deferred: Optional[List[Callable[[], None]]] = None
                      ) -> MemberResult:
    """Blocksize/grid-unaligned member: RTN for starved lanes, else skip.

    A starved expert still gets the per-group grid when its input dim
    aligns to ``group_size`` (only GPTQ/RPIQ need ``blocksize``
    alignment); otherwise one full-row group, no stored grid. A stacked
    member mixes per-lane outcomes via the mask; its grid is stored only
    when every lane produced one (all-starved + aligned). Fallback
    records carry no device values, but with ``deferred`` they still
    queue behind the group closures so report ORDER matches serial.
    """
    recs: List[LinearRecord] = []

    def _emit():
        if deferred is None:
            report.linears.extend(recs)
        else:
            deferred.append(lambda: report.linears.extend(recs))

    shape = m.wshape
    aligned = shape[1] % qc.group_size == 0
    gsz = qc.group_size if aligned else shape[1]
    sv = m.starved_mask()
    if not m.stacked:
        if m.starved:
            res = rtn_quantize(jnp.asarray(m.w_oi, jnp.float32),
                               bits=qc.bits, group_size=gsz,
                               symmetric=qc.symmetric)
            recs.append(LinearRecord(
                m.name, shape, 0.0, [], 0.0, 0, "rtn-fallback"))
            _emit()
            return MemberResult(m.name, res.w_q,
                                (res.scales, res.zeros) if aligned else None)
        recs.append(LinearRecord(
            m.name, shape, 0.0, [], 0.0, 0, "skipped"))
        _emit()
        return MemberResult(m.name, None, None)
    for li, lname in enumerate(m.lane_names):
        recs.append(LinearRecord(
            lname, shape, 0.0, [], 0.0, 0,
            "rtn-fallback" if sv[li] else "skipped"))
    _emit()
    if not sv.any():
        return MemberResult(m.name, None, None)
    w = jnp.asarray(m.w_oi, jnp.float32)
    res = rtn_quantize_batched(w, bits=qc.bits, group_size=gsz,
                               symmetric=qc.symmetric)
    svj = jnp.asarray(sv)[:, None, None]
    w_q = jnp.where(svj, res.w_q, w)              # skipped lanes keep fp
    grid = ((res.scales, res.zeros)
            if aligned and bool(sv.all()) else None)
    return MemberResult(m.name, w_q, grid)


def execute_plan(qc: QuantConfig, plan: QuantPlan, report: QuantReport,
                 rpiq_enabled: bool = True,
                 batched: Optional[bool] = None,
                 mesh=None, sync: bool = True,
                 deferred: Optional[List[Callable[[], None]]] = None
                 ) -> Dict[str, MemberResult]:
    """Run every group + fallback; returns {member name → MemberResult}.

    ``batched=None`` reads ``qc.batched_executor``; ``False`` forces the
    legacy per-linear dispatch (parity tests, table4 baseline).

    ``mesh`` (a ``(data, model)`` or ``(data, model, expert)``
    :class:`jax.sharding.Mesh`) turns on sharded group execution: every
    batched group whose lane count / Cout pass the divisibility guards
    runs mesh-wide (DESIGN.md §2.6); groups made entirely of stacked
    expert slabs additionally offer their lane axis to the ``expert``
    mesh axis (expert parallelism — per-expert Hessians already live
    with their expert, so the placement adds no collectives). The rest —
    and the whole plan when ``mesh`` is None or ``batched`` is False —
    keep the single-device paths.

    ``sync=False`` + ``deferred`` is the overlap schedule's contract
    (core/stream.py): batched stage dispatches stay async and the
    report-record closures (which synchronize via ``np.asarray``) queue
    into ``deferred`` for the caller's report boundary. The legacy
    per-linear path stays per-stage synchronized regardless — it exists
    as the timing baseline.
    """
    if batched is None:
        batched = qc.batched_executor
    out: Dict[str, MemberResult] = {}
    for group in plan.groups:
        if batched:
            gshard = quant_group_sharding(
                mesh, sum(m.lanes for m in group.members), group.key[0],
                expert_stacked=all(m.stacked for m in group.members))
            results = _execute_group_batched(qc, group, report, rpiq_enabled,
                                             gshard, sync=sync,
                                             deferred=deferred)
        else:
            results = [_execute_member_singleton(qc, m, report, rpiq_enabled)
                       for m in group.members]
        for r in results:
            out[r.name] = r
    for m in plan.fallbacks:
        r = _execute_fallback(qc, m, report, deferred=deferred)
        out[r.name] = r
    return out
