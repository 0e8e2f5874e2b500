"""The RPIQ model-quantization pipeline (the paper's end-to-end procedure).

Sequential layer-wise calibration, exactly as GPTQ/AutoGPTQ practice it and
the paper assumes:

  1. embed every calibration batch → residual streams ``hs``;
  2. for each transformer layer (segment-element by element):
     a. **capture** — run the layer over all batches with a :class:`Tap`
        that streams each named linear's inputs into its Hessian
        (eq. 9, ``H += X_bᵀX_b``) and keeps only the **last** batch's
        inputs resident (single-instance paradigm, eq. 11). With
        ``quant.jit_capture`` (default) the forward is COMPILED — the tap
        collects tracers inside the jit and the inputs come back as
        outputs — and cached per layer signature, so repeated layers
        reuse the compiled forward (``False`` = legacy eager capture);
     b. **plan** — :func:`repro.core.plan.build_plan` turns the captured
        linears (dense taps AND stacked MoE expert slices) into a
        :class:`~repro.core.plan.QuantPlan`: members grouped by
        ``(shape, n_last, group_size, blocksize, bits, symmetric)``;
     c. **execute** — each group runs through the *batched* executors
        (``gptq_quantize_batched`` stage 1, eq. 10; ``rpiq_refine_batched``
        stage 2, eq. 4–8, 12–14, 19–23): weights/Hessians/instances are
        stacked on a leading axis and quantized in ONE dispatch per stage
        per group instead of one per linear (``quant.batched_executor=False``
        restores per-linear dispatch — same plan, singleton executors);
     d. **scatter** the on-grid results back into the param tree and re-run
        the layer to **propagate quantized activations** to the next layer
        (so later Hessians see the quantized network — GPTQ semantics);
  3. MoE layers: the router/shared-expert linears tap normally; routed
     expert FFNs get **per-expert Hessians from their routed tokens** via
     ``moe.dispatch``, accumulated as ONE stacked (E, d, d) HessianState
     (capacity-padded zero rows contribute nothing to ``XᵀX``). All E
     experts of a weight join the plan as one group of E stacked members —
     w_gate and w_up even share a 2E-member group — and experts that saw
     fewer than one group of tokens become an RTN fallback *mask inside
     the group* (recorded in the report as before).

The walk itself is architecture-agnostic: both decoder-only and enc-dec
models (MoE layers included) describe themselves as ONE
:class:`~repro.core.stream.LayerWalker` — a flat list of
``LayerStep{apply_fn, param_subtree, hs_slot, signature}`` items built by
:func:`_walker_decoder_only` / :func:`_walker_encdec` — and the scheduler
in :mod:`repro.core.stream` drains it. ``quant.pipeline`` selects the
schedule: ``serial`` alternates capture/execute/propagate per layer with
per-stage synchronized timings; ``overlap`` keeps executor dispatches
async and speculatively runs the next layer's capture forward on the
pre-quantization stream, repairing it exactly after the scatter lands
(DESIGN.md §2.7). Both schedules produce bitwise-identical artifacts.

Returns float params whose quantized linears hold *on-grid* values plus a
``QuantReport`` (per-linear Γ histories = paper Table 5 / Fig. 5) and a
packer to int4 serving artifacts (QuantizedTensor leaves).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import Config
from repro.core import faults
from repro.core import hessian as hess
from repro.core import plan as qplan
from repro.core import spans
from repro.core import stream as qstream
from repro.core.plan import (LinearRecord, MemberResult,  # noqa: F401
                             PlanMember, QuantReport)
from repro.core.quant import QuantizedTensor, pack_int4
from repro.kernels import ops as kops
from repro.core.stream import LayerStep, LayerWalker, StreamSwitch
from repro.models import transformer as T
from repro.models import moe as moe_mod
from repro.models.linear import Tap
from repro.models.layers import embed, norm, sinusoidal_positions


# ---------------------------------------------------------------------------
# Jitted calibration forward (capture + propagate)
#
# The capture/propagate forwards used to run eagerly, op by op — the
# second wall-clock dominator after the executors (benchmarks/
# table4_time.py).  ``_layer_forward_jit`` compiles them instead: the Tap
# opens INSIDE the traced function in collect-tracers mode, so the tapped
# layer inputs come back as ordinary jit outputs.  Entries are cached per
# (fwd_key, batch index, layer-signature) for ONE ``quantize_model`` run
# — repeated layers (same spec + shapes) reuse the compiled forward, and
# scoping the cache to the run keeps closure constants (positions,
# encoder outputs) from leaking across models.  Batch-independent layers
# collapse the batch index to 0; the encoder-decoder decoder bakes
# ``enc_out[bi]`` into the trace, so it keys per batch.
# ---------------------------------------------------------------------------

class ForwardCache(dict):
    """Per-run compiled-forward cache with hit/miss counters.

    A plain dict keyed by (fwd_key, batch-index, collect, layer
    signature); the counters make capture-forward reuse observable next
    to :func:`repro.core.plan.executor_cache_stats` (the overlap
    scheduler's speculative captures share entries with their exact
    repairs, so speculation never doubles compiles).
    """

    def __init__(self):
        super().__init__()
        self.hits = 0
        self.misses = 0

    def get(self, key, default=None):
        fn = super().get(key, default)
        if fn is None:
            self.misses += 1
        else:
            self.hits += 1
        return fn

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses}


_LAST_FWD_STATS: Dict[str, int] = {"hits": 0, "misses": 0}


def capture_cache_stats() -> Dict[str, int]:
    """{hits, misses} of the capture/propagate forward cache of the most
    recent :func:`quantize_model` run (API symmetry with
    ``plan.executor_cache_stats()``). Only the counters outlive the run —
    the cache itself (compiled forwards + their baked closure constants)
    stays run-scoped and is dropped with it."""
    return dict(_LAST_FWD_STATS)


def _tree_signature(tree) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),
            tuple((tuple(l.shape), str(l.dtype)) for l in leaves))


def _layer_forward_jit(fwd_cache: Dict, fwd_key: Tuple, apply_fn,
                       params: Dict, h: jax.Array, bi: int,
                       batch_dependent: bool, collect: bool = True):
    """Run one layer forward compiled; returns (h_out, {name: [inputs]}).

    ``collect=False`` (the propagate pass) compiles a tap-less forward —
    returning the tapped inputs as jit outputs would force XLA to
    materialize every linear's input buffer the caller then discards.
    """
    key_bi = bi if batch_dependent else 0
    key = (fwd_key, key_bi, collect, _tree_signature(params), h.shape,
           str(h.dtype))
    fn = fwd_cache.get(key)
    if fn is not None:
        return fn(params, h)

    def fwd(p, hh, _bi=bi):
        if not collect:
            return apply_fn(p, hh, _bi), {}
        tap = Tap(collect_tracers=True)
        with tap:
            out = apply_fn(p, hh, _bi)
        return out, {k: list(v) for k, v in tap.records.items()}
    # the first call traces, lowers and compiles (or loads) the forward
    with spans.span(spans.FWD_BUILD):
        fn = fwd_cache[key] = jax.jit(fwd)
        return fn(params, h)


def _resolve(tree: Dict, dotted: str):
    node = tree
    for part in dotted.split("."):
        node = node[part]
    return node


def _linear_names_in(tree: Dict, prefix: str = "") -> List[str]:
    """Dotted paths of {w:...} dense params inside a layer subtree."""
    out = []
    for k, v in tree.items():
        path = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            if "w" in v and not isinstance(v["w"], dict) \
                    and getattr(v["w"], "ndim", 0) == 2:
                out.append(path)
            else:
                out.extend(_linear_names_in(v, path))
    return out


_QUANT_SUBTREES = ("mixer", "mlp", "xattn")   # norms/embeds stay fp
_MOE_WNAMES = ("w_gate", "w_up", "w_down")


def _is_moe_layer(layer_params: Dict) -> bool:
    mlp = layer_params.get("mlp")
    return isinstance(mlp, dict) and "w_gate" in mlp


def _layer_repair_sound(layer_params: Dict) -> bool:
    """Is the capture-ahead Hessian repair sound for this layer signature?

    Every current signature is. Dense layers re-propagate their taps on
    the post-scatter stream through the same compiled entries (the exact
    repair). Routed-MoE layers — formerly the exception — now repair at
    the *plan* level: the speculative pass precomputes each batch's
    dispatch plan, and ``_moe_members`` re-runs only the routing head on
    the true stream, reusing the sort/capacity structure wholesale when
    no assignment flipped and re-sorting flipped batches (bounded by
    ``quant.moe_flip_budget``). Kept as a predicate so tests can
    monkeypatch a forced-unsound lane (tests/test_pipeline_stream.py).
    """
    del layer_params
    return True


def _moe_members(cfg: Config, p_moe: Dict, xs: List[jax.Array],
                 name: str, report: Optional[QuantReport] = None,
                 stats: Optional[Dict] = None,
                 spec_routes: Optional[List] = None,
                 layer_name: str = "layer") -> List[PlanMember]:
    """Plan members for the routed experts (paper's method per expert).

    ``xs``: per-calibration-batch flat MoE block inputs (T, d), collected
    from the router tap. Per-expert Hessians accumulate as one stacked
    (E, ·, ·) state per input kind — no per-expert Python loop; the
    starved-expert check becomes a flag the executor applies as a mask.

    ``spec_routes`` (overlap scheduler): dispatch plans the speculative
    capture computed on the PRE-quantization stream. Routing is always
    recomputed here on the true stream — only the routing head (router
    matmul + top-k); the sort/capacity *structure* is a pure function of
    the expert ids (models/moe.py), so batches whose assignments did not
    flip reuse the speculative structure bitwise and only flipped
    batches re-sort. Every Hessian accumulates true-stream values
    through the same ops as serial either way, which is what keeps
    overlap bitwise-equal to serial on routed MoE.
    """
    qc = cfg.quant
    mc = cfg.model
    e = mc.moe.num_experts
    d, f = p_moe["w_gate"].shape[1:]
    xs_c = [xt.astype(jnp.dtype(mc.dtype)) for xt in xs]

    def bump(key: str, n: int = 1) -> None:
        if stats is not None and isinstance(stats.get(key), int):
            stats[key] += int(n)

    plans: Optional[List[moe_mod.RoutePlan]] = None
    if spec_routes is not None and len(spec_routes) == len(xs_c):
        heads = [moe_mod.route_head(mc, p_moe, xt) for xt in xs_c]
        flips = np.asarray(jnp.stack(
            [jnp.sum(h.experts != sp.experts)
             for h, sp in zip(heads, spec_routes)]))    # one host sync
        n_assign = sum(h.experts.size for h in heads)
        n_flips = int(flips.sum())
        bump("moe_spec_layers")
        bump("moe_flipped_assignments", n_flips)
        bump("moe_assignments", n_assign)
        if n_assign and n_flips / n_assign > qc.moe_flip_budget:
            # too much of the routing moved — the speculative plans buy
            # nothing; discard them wholesale and re-plan serially
            bump("fallback_flip_budget")
            bump("serial_fallbacks")
        else:
            plans = []
            for h, sp, nf in zip(heads, spec_routes, flips):
                if nf == 0:
                    plans.append(moe_mod.reuse_plan(sp, h))
                    bump("moe_plan_reuses")
                else:
                    plans.append(moe_mod.plan_from_head(mc, h))
                    bump("moe_flip_repairs")
    if plans is None:
        plans = [moe_mod.route(mc, p_moe, xt) for xt in xs_c]

    # stream dispatch over batches: stacked per-expert Hessians for gate/up
    # (input d) and for down (input f, needs the expert mid activations).
    from repro.models.layers import _act
    H_in = hess.init_hessian(d, batch=e)
    H_mid = hess.init_hessian(f, batch=e)
    x_last_in: Optional[jax.Array] = None
    x_last_mid: Optional[jax.Array] = None
    for plan, xt in zip(plans, xs_c):
        buf = moe_mod.apply_route(plan, xt)             # (E, C, d)
        g = jnp.einsum("ecd,edf->ecf", buf.astype(jnp.float32),
                       p_moe["w_gate"].astype(jnp.float32))
        u = jnp.einsum("ecd,edf->ecf", buf.astype(jnp.float32),
                       p_moe["w_up"].astype(jnp.float32))
        mid = _act(mc.act, g) * u                       # (E, C, f)
        H_in = hess.accumulate(H_in, buf)
        H_mid = hess.accumulate(H_mid, mid)
        x_last_in, x_last_mid = buf, mid
    last_counts = plans[-1].counts

    # routed-count + capacity-drop tallies in ONE host sync (the per-batch
    # np.asarray round-trip this loop used to make stalled the async
    # queue every batch — the stall the overlap schedule exists to avoid)
    count_stack = jnp.stack([p.counts for p in plans])  # (B, E)
    dropped = jnp.stack([jnp.sum(~p.keep) for p in plans])
    tallies = np.asarray(jnp.concatenate(
        [jnp.sum(count_stack, axis=0),
         jnp.sum(dropped)[None].astype(jnp.int32)]), np.int64)
    real_counts, n_dropped = tallies[:e], int(tallies[e])
    bump("moe_dropped_tokens", n_dropped)
    if report is not None:
        # capacity-dropped tokens vanish from the per-expert Hessians by
        # construction — record them so calibration coverage is honest
        report.moe_capacity_dropped[layer_name] = \
            report.moe_capacity_dropped.get(layer_name, 0) + n_dropped

    members: List[PlanMember] = []
    for wname, Hst, xl in (("w_gate", H_in, x_last_in),
                           ("w_up", H_in, x_last_in),
                           ("w_down", H_mid, x_last_mid)):
        # zero-padded capacity rows contribute nothing to XᵀX; real routed
        # token counts drive both the starvation check and the eq.-13
        # rescale. One stacked member per weight: the expert axis stays a
        # whole (E, ·, ·) slab from capture through scatter.
        members.append(PlanMember(
            f"{name}.{wname}",
            jnp.swapaxes(jnp.asarray(p_moe[wname], jnp.float32), -1, -2),
            hess.HessianState(Hst.H,
                              jnp.asarray(real_counts, jnp.int32)),
            xl, x_count=last_counts.astype(jnp.int32),
            starved=real_counts < qc.group_size,
            names=[f"{name}.{wname}[{ei}]" for ei in range(e)]))
    return members


def _scatter_moe(p_moe: Dict, results: Dict[str, MemberResult],
                 name: str) -> Dict:
    """Reassemble stacked expert weights (+grids) from member results."""
    new = dict(p_moe)
    for wname in _MOE_WNAMES:
        res = results[f"{name}.{wname}"]
        if res.w_q is None:                             # skipped (unaligned)
            continue
        new[wname] = jnp.swapaxes(res.w_q, -1, -2).astype(
            p_moe[wname].dtype)
        if res.grid is not None:
            new[f"{wname}_qscales"] = res.grid[0]
            new[f"{wname}_qzeros"] = res.grid[1]
    return new


# ---------------------------------------------------------------------------
# Per-step primitives (capture / plan / scatter / propagate)
#
# These are the stage bodies the stream scheduler composes — the serial
# schedule chains them per layer, the overlap schedule interleaves them
# across adjacent layers (core/stream.py).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CaptureResult:
    """One layer's tapped calibration state.

    ``h_out`` holds the capture forward's per-batch outputs — the layer's
    PRE-quantization residual stream, which the overlap scheduler feeds
    to the next step's speculative capture (it exists before the
    executor finishes). Collected only on request: the serial schedule —
    and the speculative pass itself — would otherwise pin n_batches
    activation arrays per step for nothing.

    ``spec_routes`` is set only by a *speculative* capture of a routed-MoE
    layer: the per-batch dispatch plans computed on the pre-quantization
    stream, which ``_moe_members`` verifies against recomputed routing on
    the true stream and reuses where no assignment flipped.
    """
    hessians: Dict[str, hess.HessianState]
    last_x: Dict[str, jax.Array]
    moe_xs: List[jax.Array]
    h_out: Optional[List[jax.Array]]
    is_moe: bool
    spec_routes: Optional[List] = None


@spans.spanned(spans.CAPTURE)
def capture_layer(cfg: Config, step: LayerStep, hs: List[jax.Array],
                  fwd_cache: Optional[Dict] = None,
                  speculative: bool = False,
                  collect_h_out: bool = False) -> CaptureResult:
    """Stage (a): stream Hessians over all batches, keep last inputs.

    ``speculative`` marks a capture-ahead pass (overlap scheduler): same
    dispatches on a different stream, dense results discarded by the
    exact repair. For a routed-MoE layer the speculative pass
    additionally dispatches the per-batch routing plans on its stream
    (``CaptureResult.spec_routes``) — the structure the plan-level
    flip-repair reuses when the post-scatter routing agrees.
    ``collect_h_out`` retains the per-batch forward outputs (the
    pre-quantization stream the scheduler speculates on).
    """
    faults.fire("stream.capture_forward")
    qc = cfg.quant
    layer_params = step.resolve_params()
    use_jit = qc.jit_capture and fwd_cache is not None
    is_moe = _is_moe_layer(layer_params)
    hessians: Dict[str, hess.HessianState] = {}
    last_x: Dict[str, jax.Array] = {}
    moe_xs: List[jax.Array] = []     # per-batch MoE block inputs (router tap)

    targets = set()
    for sub in _QUANT_SUBTREES:
        if sub in layer_params:
            targets.update(f"{sub}.{n}" if n else sub
                           for n in _linear_names_in(layer_params[sub]))
    # the router stays full-precision (standard MoE-PTQ practice; its tap is
    # only used to collect the block inputs for the per-expert Hessians)
    targets.discard("mlp.router")

    def on_record(name: str, x: jax.Array):
        if name == "mlp.router":
            moe_xs.append(x.reshape(-1, x.shape[-1]))
            return
        if name not in targets:
            return
        x2 = x.reshape(-1, x.shape[-1])
        if name not in hessians:
            hessians[name] = hess.init_hessian(x2.shape[1])
        hessians[name] = hess.accumulate(hessians[name], x2)
        last_x[name] = x2        # overwritten per batch → last batch stays

    h_out: Optional[List[jax.Array]] = [] if collect_h_out else None
    for bi, h in enumerate(hs):
        if use_jit:
            out, recs = _layer_forward_jit(fwd_cache, step.fwd_key,
                                           step.apply_fn, layer_params, h,
                                           bi, step.batch_dependent)
            for name, xs in recs.items():
                for x in xs:
                    on_record(name, x)
        else:
            with Tap(on_record=on_record):
                out = step.apply_fn(layer_params, h, bi)
        if collect_h_out:
            h_out.append(out)
    spec_routes: Optional[List] = None
    if speculative and is_moe and moe_xs:
        # dispatch the routing plans on the speculative stream while the
        # previous step's executor is in flight — async device work; the
        # repair verifies them against the true stream at plan time
        dtype = jnp.dtype(cfg.model.dtype)
        spec_routes = [moe_mod.route(cfg.model, layer_params["mlp"],
                                     xt.astype(dtype)) for xt in moe_xs]
    return CaptureResult(hessians, last_x, moe_xs, h_out, is_moe,
                         spec_routes)


@spans.spanned(spans.PLAN)
def plan_layer(cfg: Config, step: LayerStep, cap: CaptureResult,
               hs: List[jax.Array], report: Optional[QuantReport] = None,
               stats: Optional[Dict] = None,
               spec_routes: Optional[List] = None
               ) -> Tuple[Dict, List[str], "qplan.QuantPlan"]:
    """Stage (b): dense taps + stacked MoE expert slices → QuantPlan.

    ``spec_routes`` threads the speculative dispatch plans from the
    overlap scheduler's capture-ahead to the MoE flip-repair; ``report``/
    ``stats`` receive capacity-drop and repair counters when given.
    Returns (fresh param-subtree copy, sorted dense names, plan).
    """
    qc = cfg.quant
    new_params = jax.tree_util.tree_map(lambda x: x, step.resolve_params())
    members: List[PlanMember] = []
    dense_names = sorted(cap.hessians.keys())
    for name in dense_names:
        node = _resolve(new_params, name)
        members.append(PlanMember(
            name, jnp.asarray(node["w"], jnp.float32).T, cap.hessians[name],
            cap.last_x[name], x_count=None))
    if cap.is_moe:
        assert len(cap.moe_xs) == len(hs), "router tap missed batches"
        members.extend(_moe_members(cfg, new_params["mlp"], cap.moe_xs,
                                    "mlp", report=report, stats=stats,
                                    spec_routes=spec_routes,
                                    layer_name=step.name))
    return new_params, dense_names, qplan.build_plan(qc, members)


def scatter_layer(new_params: Dict, dense_names: List[str],
                  cap: CaptureResult,
                  results: Dict[str, MemberResult]) -> Dict:
    """Stage (d, first half): write on-grid results back into the subtree."""
    for name in dense_names:
        res = results[name]
        if res.w_q is None:
            continue                                    # skipped: keep fp
        node = _resolve(new_params, name)
        node["w"] = res.w_q.T.astype(node["w"].dtype)
        if res.grid is not None:
            # stage-1 grid travels with the weight → exact int4 packing
            node["qscales"], node["qzeros"] = res.grid
    if cap.is_moe:
        new_params["mlp"] = _scatter_moe(new_params["mlp"], results, "mlp")
    return new_params


def propagate_layer(cfg: Config, step: LayerStep, new_params: Dict,
                    hs: List[jax.Array],
                    fwd_cache: Optional[Dict] = None) -> List[jax.Array]:
    """Stage (d, second half): re-run the layer with quantized params so
    the next layer's Hessians see the quantized network (same compiled
    forward family; the quantized params carry extra grid leaves, so they
    key their own cross-layer cache entry)."""
    use_jit = cfg.quant.jit_capture and fwd_cache is not None
    if use_jit:
        return [_layer_forward_jit(fwd_cache, step.fwd_key, step.apply_fn,
                                   new_params, h, bi, step.batch_dependent,
                                   collect=False)[0]
                for bi, h in enumerate(hs)]
    return [step.apply_fn(new_params, h, bi) for bi, h in enumerate(hs)]


def quantize_layer(cfg: Config, layer_params: Dict, hs: List[jax.Array],
                   apply_fn, report: QuantReport,
                   fwd_cache: Optional[Dict] = None,
                   fwd_key: Tuple = ("layer",),
                   batch_dependent: bool = False,
                   mesh=None) -> Tuple[Dict, List]:
    """Quantize one layer's linears via the plan, then propagate (serial).

    The single-layer convenience wrapper over the per-step primitives
    above — what the serial schedule does per step. ``apply_fn(params, h,
    batch_index) -> h_out`` runs the layer; ``mesh`` forwards to
    :func:`repro.core.plan.execute_plan` for sharded group execution
    (capture itself stays single-device — only executor work scales with
    the mesh). Returns (new_layer_params, new_hs).
    """
    step = LayerStep(name="layer", params=layer_params, apply_fn=apply_fn,
                     hs_slot="h", fwd_key=fwd_key, store=lambda p: None,
                     batch_dependent=batch_dependent)
    cap = capture_layer(cfg, step, hs, fwd_cache)
    new_params, dense_names, plan = plan_layer(cfg, step, cap, hs,
                                               report=report)
    results = qplan.execute_plan(cfg.quant, plan, report, mesh=mesh)
    scatter_layer(new_params, dense_names, cap, results)
    return new_params, propagate_layer(cfg, step, new_params, hs, fwd_cache)


# ---------------------------------------------------------------------------
# LayerWalkers: each architecture described once, as data
#
# A walker builder turns (cfg, params, calib) into streams + a flat list
# of LayerSteps (+ StreamSwitch fences) + a finalizer. Builders must not
# read stream VALUES while building (closures only bake static context:
# specs, positions, the params they quantize) — stream-dependent work
# (e.g. the encoder final norm feeding cross-attention) happens inside a
# StreamSwitch at its place in the walk, which is what lets the overlap
# scheduler look one step ahead safely.
# ---------------------------------------------------------------------------

def _walker_decoder_only(cfg: Config, params: Dict, calib) -> LayerWalker:
    mc = cfg.model
    dtype = jnp.dtype(mc.dtype)
    hs = []
    for b in calib:
        h = embed(params["embed"], b["tokens"], dtype)
        if b.get("embeds") is not None:
            h = jnp.concatenate([b["embeds"].astype(dtype), h], axis=1)
        hs.append(h)
    seqs = [h.shape[1] for h in hs]
    assert len(set(seqs)) == 1, "calibration batches must share seq_len"
    b0, s0, _ = hs[0].shape
    positions = jnp.arange(s0, dtype=jnp.int32)[None, :].repeat(b0, 0)

    items: List[qstream.WalkItem] = []
    collected: List[List[Dict]] = []    # per segment: per-element subtrees
    li = 0
    for seg, seg_params in zip(T.segments(mc), params["blocks"]):
        elems: List[Dict] = [dict() for _ in range(seg.count)]
        collected.append(elems)
        for c in range(seg.count):
            for s_i, spec in enumerate(seg.specs):

                def apply_fn(p, h, bi, _spec=spec):
                    out, _ = T.layer_forward(mc, _spec, p, h, positions)
                    return out

                li += 1
                items.append(LayerStep(
                    name=f"layer {li}",
                    # lazy slice: materialized at the step's turn, released
                    # after it — the walk never pins all pre-quant slices
                    params=(lambda _sp=seg_params, _c=c, _k=f"sub{s_i}":
                            T._seg_take(_sp, _c)[_k]),
                    apply_fn=apply_fn,
                    hs_slot="h", fwd_key=("dec", str(spec)),
                    store=(lambda p, _e=elems[c], _k=f"sub{s_i}":
                           _e.__setitem__(_k, p))))

    def finalize() -> Dict:
        out = dict(params)
        out["blocks"] = [T._stack_trees(elems) for elems in collected]
        return out

    return LayerWalker(streams={"h": hs}, items=items, finalize=finalize)


def _walker_encdec(cfg: Config, params: Dict, calib) -> LayerWalker:
    mc = cfg.model
    dtype = jnp.dtype(mc.dtype)
    # ----- encoder stream -----
    hs = []
    for b in calib:
        fr = b["frames"].astype(dtype)
        hs.append(fr + sinusoidal_positions(fr.shape[1], mc.d_model
                                            )[None].astype(dtype))
    se = hs[0].shape[1]
    b0 = hs[0].shape[0]
    enc_pos = jnp.arange(se, dtype=jnp.int32)[None, :].repeat(b0, 0)

    items: List[qstream.WalkItem] = []
    n_enc = jax.tree_util.tree_leaves(
        params["encoder"]["layers"])[0].shape[0]
    enc_elems: List[Optional[Dict]] = [None] * n_enc
    for i in range(n_enc):

        def enc_apply(p, h, bi):
            hn = norm(mc, p["norm1"], h)
            from repro.models import attention as attn
            y = attn.attention_forward(mc, p["mixer"], hn, enc_pos,
                                       causal=False, use_rope=False,
                                       name="mixer")
            h = h + y
            hn = norm(mc, p["norm2"], h)
            from repro.models.layers import mlp as mlp_fn
            return h + mlp_fn(mc, p["mlp"], hn, name="mlp")

        items.append(LayerStep(
            name=f"enc {i + 1}",
            params=(lambda _i=i: T._seg_take(params["encoder"]["layers"],
                                             _i)),
            apply_fn=enc_apply, hs_slot="enc", fwd_key=("enc",),
            store=(lambda p, _i=i: enc_elems.__setitem__(_i, p))))

    # ----- enc → dec fence: finalize the (quantized) encoder stream into
    # the cross-attention memory, open the decoder stream -----
    dhs = []
    for b in calib:
        tk = b["tokens"]
        h = embed(params["embed"], tk, dtype)
        dhs.append(h + sinusoidal_positions(tk.shape[1], mc.d_model
                                            )[None].astype(dtype))
    sd = dhs[0].shape[1]
    dec_pos = jnp.arange(sd, dtype=jnp.int32)[None, :].repeat(b0, 0)
    ctx: Dict[str, List[jax.Array]] = {}

    def switch(streams: Dict[str, List[jax.Array]]) -> None:
        ctx["enc_out"] = [norm(mc, params["encoder"]["final_norm"], h)
                          for h in streams["enc"]]
        streams["dec"] = dhs

    items.append(StreamSwitch(name="enc→dec", run=switch))

    n_dec = jax.tree_util.tree_leaves(
        params["decoder"]["layers"])[0].shape[0]
    dec_elems: List[Optional[Dict]] = [None] * n_dec
    for i in range(n_dec):

        def dec_apply(p, h, bi):
            from repro.models import attention as attn
            from repro.models.layers import mlp as mlp_fn
            llp = p["layer"]
            hn = norm(mc, llp["norm1"], h)
            y = attn.attention_forward(mc, llp["mixer"], hn, dec_pos,
                                       causal=True, use_rope=False,
                                       name="layer.mixer")
            h = h + y
            hn = norm(mc, p["xnorm"], h)
            kv = attn.cross_attention_kv(mc, p["xattn"], ctx["enc_out"][bi],
                                         "xattn")
            h = h + attn.cross_attention(mc, p["xattn"], hn, kv, "xattn")
            hn = norm(mc, llp["norm2"], h)
            return h + mlp_fn(mc, llp["mlp"], hn, name="layer.mlp")

        # enc_out[bi] is baked into the trace → key per batch index
        items.append(LayerStep(
            name=f"dec {i + 1}",
            params=(lambda _i=i: T._seg_take(params["decoder"]["layers"],
                                             _i)),
            apply_fn=dec_apply, hs_slot="dec", fwd_key=("xdec",),
            batch_dependent=True,
            store=(lambda p, _i=i: dec_elems.__setitem__(_i, p))))

    def finalize() -> Dict:
        out = dict(params)
        out["encoder"] = {"layers": T._stack_trees(enc_elems),
                          "final_norm": params["encoder"]["final_norm"]}
        out["decoder"] = {"layers": T._stack_trees(dec_elems),
                          "final_norm": params["decoder"]["final_norm"]}
        return out

    return LayerWalker(streams={"enc": hs}, items=items, finalize=finalize)


_MESH_FROM_CONFIG = object()     # sentinel: resolve the quant.mesh knob


def quantize_model(cfg: Config, params: Dict,
                   calib: List[Dict[str, jax.Array]],
                   verbose: bool = False,
                   mesh=_MESH_FROM_CONFIG) -> Tuple[Dict, QuantReport]:
    """Quantize every transformer layer of a decoder-only or enc-dec model.

    ``calib``: list of batch dicts ({tokens, embeds?/frames?}); the last one
    is the single instance for stage 2.

    ``mesh``: a ``(data, model)`` Mesh for sharded group execution
    (DESIGN.md §2.6), or None to force single-device execution; left
    unset, the ``quant.mesh`` knob is resolved through
    :func:`repro.launch.mesh.make_quant_mesh` (default "off" = single
    device).

    The walk runs under ``quant.pipeline`` (serial | overlap — see
    :mod:`repro.core.stream`); artifacts are schedule-independent.
    """
    global _LAST_FWD_STATS
    t_start = time.perf_counter()
    report = QuantReport()
    with spans.recording(report.spans), spans.span(spans.JOB):
        if mesh is _MESH_FROM_CONFIG:
            from repro.launch.mesh import make_quant_mesh
            mesh = make_quant_mesh(cfg.quant.mesh)

        fwd_cache = ForwardCache()   # per-run compiled forwards (jit_capture)
        build = (_walker_encdec if cfg.model.is_encoder_decoder
                 else _walker_decoder_only)
        with spans.span(spans.WALKER):
            walker = build(cfg, params, calib)
        fb0 = kops.fallback_stats()
        try:
            out = qstream.run_walker(cfg, walker, report,
                                     fwd_cache=fwd_cache, mesh=mesh,
                                     verbose=verbose)
        finally:
            # only the counters outlive the run — keeping the cache itself
            # alive would pin every compiled forward and its baked closure
            # constants (positions, enc_out) past the model they belong to
            _LAST_FWD_STATS = fwd_cache.stats()
    # auto→xla kernel downgrades observed during THIS run (delta against
    # the process-wide counters): surfaced so a budget-driven fallback is
    # visible in the report instead of silently changing the backend
    report.kernel_fallbacks = {
        k: v - fb0.get(k, 0) for k, v in kops.fallback_stats().items()
        if v - fb0.get(k, 0)}
    report.seconds_total = time.perf_counter() - t_start
    return out, report


# ---------------------------------------------------------------------------
# Packing to serving artifacts
# ---------------------------------------------------------------------------

def pack_for_serving(cfg: Config, params_q: Dict) -> Dict:
    """Replace quantized-linear float weights with int4 QuantizedTensor.

    Weights are re-gridded with fresh (scale, zero) per group — the values
    are already on a 4-bit grid from the pipeline, so this round-trips
    exactly (asserted in tests). Norms/embeddings stay fp.
    """
    qc = cfg.quant

    from repro.core.quant import QuantParams, compute_qparams, quantize_codes

    def pack_generic(w: jax.Array, scales=None,
                     zeros=None) -> QuantizedTensor:
        """(..., in, out) float → (..., out, in//2)-packed QuantizedTensor.

        Leading dims cover scan-stacked layers and/or the expert axis; the
        math is fully vectorized (no per-expert Python loops — deepseek has
        58×256 expert matrices). When the pipeline carried the stage-1 grid
        (qscales/qzeros), packing on it round-trips the refined weights
        EXACTLY; otherwise the grid is recomputed (lossy only for weights
        not already on a grid, e.g. fp checkpoints packed directly).
        """
        w_oi = jnp.swapaxes(jnp.asarray(w, jnp.float32), -1, -2)
        lead = w_oi.shape[:-2]
        o, i = w_oi.shape[-2:]
        g = i // qc.group_size
        w2 = w_oi.reshape(-1, i)
        if scales is not None:
            qp = QuantParams(jnp.asarray(scales, jnp.float32)
                             .reshape(-1, g),
                             jnp.asarray(zeros, jnp.float32).reshape(-1, g))
        else:
            qp = compute_qparams(w2, qc.bits, qc.group_size)
        codes = quantize_codes(w2, qp, qc.bits, qc.group_size)
        packed = pack_int4(codes).reshape(*lead, o, i // 2)
        return QuantizedTensor(packed,
                               qp.scales.reshape(*lead, o, g),
                               qp.zeros.reshape(*lead, o, g),
                               (*lead, o, i), qc.bits, qc.group_size)

    def walk(tree, path=""):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                sub = f"{path}.{k}"
                if k in ("qscales", "qzeros") or k.endswith("_qscales") \
                        or k.endswith("_qzeros"):
                    continue                      # consumed by the packer
                if (k == "w" and getattr(v, "ndim", 0) >= 2
                        and any(s in path for s in _QUANT_SUBTREES)
                        and v.shape[-2] % qc.group_size == 0
                        and "router" not in path):
                    out[k] = pack_generic(v, tree.get("qscales"),
                                          tree.get("qzeros"))
                elif (k in ("w_gate", "w_up", "w_down")
                      and getattr(v, "ndim", 0) >= 3
                      and v.shape[-2] % qc.group_size == 0):
                    out[k] = pack_generic(v, tree.get(f"{k}_qscales"),
                                          tree.get(f"{k}_qzeros"))
                else:
                    out[k] = walk(v, sub)
            return out
        if isinstance(tree, list):
            return [walk(v, path) for v in tree]
        return tree

    return walk(params_q)
