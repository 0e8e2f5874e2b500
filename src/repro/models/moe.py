"""Mixture-of-Experts FFN with capacity-based sort dispatch.

Design notes (DESIGN.md §2):

  - the classic Mesh-TF one-hot dispatch tensor is (tokens, E, C) — for
    deepseek-v3 train shapes that is ~1e13 elements, so we use the
    sort-based scatter instead: flatten (token, k) assignments, stable-sort
    by expert id, compute each entry's position inside its expert segment
    via ``searchsorted``, and scatter into a dense (E, C, d) buffer.
    Everything is jit-safe and O(T·K log T·K) with no (T, E) one-hots.
  - expert weights are stacked (E, ...) and sharded over the ``model`` mesh
    axis (EP); the buffer's expert axis is sharded likewise, so XLA lowers
    the scatter/gather into an all-to-all pair — the MoE collective the
    roofline tracks.
  - tokens over capacity are *dropped* (contribute nothing; the residual
    stream passes them through) — standard capacity-factor semantics.
  - router runs in float32; aux load-balance loss returned for training.
  - deepseek-style shared experts: always-on dense MLP(s) added to the
    routed output.

Per-expert FFN linears route through ``dense``-style matmuls on stacked
weights; for quantization the pipeline treats each expert's slices as
separate linears (per-expert Hessians from routed tokens — see
core/pipeline.py).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.linear import dense, init_dense
from repro.models.layers import _act, init_mlp, mlp


class MoEOutput(NamedTuple):
    y: jax.Array            # (B, S, D)
    aux_loss: jax.Array     # scalar load-balance loss
    expert_load: jax.Array  # (E,) fraction of routed tokens per expert


def init_moe(cfg: ModelConfig, key: jax.Array) -> Dict:
    m = cfg.moe
    d = cfg.d_model
    f = m.d_ff_expert
    ks = jax.random.split(key, 5)
    def stack(k, shape, scale):
        return jax.random.normal(k, shape) * scale
    p = {
        "router": init_dense(ks[0], d, m.num_experts, scale=0.02),
        # stacked expert weights: (E, in, out)
        "w_gate": stack(ks[1], (m.num_experts, d, f), d ** -0.5),
        "w_up": stack(ks[2], (m.num_experts, d, f), d ** -0.5),
        "w_down": stack(ks[3], (m.num_experts, f, d), f ** -0.5),
    }
    if m.num_shared_experts > 0:
        p["shared"] = init_mlp(cfg, ks[4], d, f * m.num_shared_experts)
    return p


def _expert_weights(w) -> jax.Array:
    """(E, in, out) bf16 view of stacked expert weights.

    Accepts a float array or a :class:`QuantizedTensor` holding (E, out,
    in//2)-packed int4 codes with (E, out, groups) scales/zeros.
    """
    from repro.core.quant import QuantizedTensor
    if isinstance(w, QuantizedTensor):
        packed = w.packed                          # (E, out, in//2)
        lo = (packed & jnp.uint8(0x0F)).astype(jnp.float32)
        hi = ((packed >> 4) & jnp.uint8(0x0F)).astype(jnp.float32)
        e, o, kh = packed.shape
        codes = jnp.stack([lo, hi], axis=-1).reshape(e, o, kh * 2)
        s = jnp.repeat(w.scales.astype(jnp.float32), w.group_size, axis=2)
        z = jnp.repeat(w.zeros.astype(jnp.float32), w.group_size, axis=2)
        return ((codes - z) * s).astype(jnp.bfloat16).transpose(0, 2, 1)
    return w.astype(jnp.bfloat16)


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * m.top_k * n_tokens / m.num_experts)
    return max(8, -(-c // 8) * 8)   # round up to 8 for TPU lane alignment


class Dispatch(NamedTuple):
    """Sort-based dispatch plan + the dense per-expert input buffer."""
    buf: jax.Array        # (E, C, d) expert inputs
    slot: jax.Array       # (T*K,) buffer row per sorted assignment
    st: jax.Array         # (T*K,) source token per sorted assignment
    sg: jax.Array         # (T*K,) gate per sorted assignment
    keep: jax.Array       # (T*K,) kept (under capacity)
    aux: jax.Array        # scalar load-balance loss
    counts: jax.Array     # (E,) routed tokens per expert (pre-capacity)


class RouteHead(NamedTuple):
    """The router's output alone: top-k assignments + renormalized gates.

    Everything *structural* about dispatch (sort order, segment
    positions, capacity keeps, buffer slots) is a pure function of
    ``experts`` — gate VALUES only weight the combine. That split is
    what makes the overlap scheduler's flip-repair sound: two streams
    whose ``experts`` agree elementwise share the entire plan bitwise.
    """
    experts: jax.Array    # (T, K) top-k expert ids
    gates: jax.Array      # (T, K) renormalized gates
    aux: jax.Array        # scalar load-balance loss


class RoutePlan(NamedTuple):
    """Full dispatch plan: head + the sort-based structural placement."""
    experts: jax.Array    # (T, K) top-k expert ids
    gates: jax.Array      # (T, K) renormalized gates
    aux: jax.Array        # scalar load-balance loss
    order: jax.Array      # (T*K,) stable argsort of the flat expert ids
    se: jax.Array         # (T*K,) sorted expert ids
    st: jax.Array         # (T*K,) source token per sorted assignment
    sg: jax.Array         # (T*K,) gate per sorted assignment
    keep: jax.Array       # (T*K,) kept (under capacity)
    slot: jax.Array       # (T*K,) buffer row (E*C = drop row)
    counts: jax.Array     # (E,) routed tokens per expert (pre-capacity)
    cap: int              # static per-expert capacity


def route_head(cfg: ModelConfig, p: Dict, xt: jax.Array,
               name: str = "moe") -> RouteHead:
    """Router forward + top-k on flat tokens xt: (T, d)."""
    m = cfg.moe
    e, k = m.num_experts, m.top_k
    # router in f32 (and tappable: the pipeline reads the MoE block inputs
    # from this tap; the router itself stays full-precision — see pipeline)
    logits = dense(p["router"], xt.astype(jnp.float32),
                   f"{name}.router")                            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)                    # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style): E * Σ_e f_e · P_e
    me = jnp.mean(probs, axis=0)                                # (E,)
    one_hot_top1 = jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32)
    fe = jnp.mean(one_hot_top1, axis=0)
    aux = e * jnp.sum(fe * me) * m.aux_loss_weight
    return RouteHead(experts, gates, aux)


def plan_from_head(cfg: ModelConfig, head: RouteHead) -> RoutePlan:
    """Structural dispatch plan from the routing head (sort + capacity)."""
    m = cfg.moe
    t, k = head.experts.shape
    e = m.num_experts
    cap = _capacity(cfg, t)
    flat_e = head.experts.reshape(-1)                           # (T*K,)
    flat_t = jnp.repeat(jnp.arange(t), k)                       # (T*K,)
    flat_g = head.gates.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = flat_t[order]
    sg = flat_g[order]
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")   # (E,)
    seg_end = jnp.searchsorted(se, jnp.arange(e), side="right")
    pos = jnp.arange(t * k) - seg_start[se]                     # pos in expert
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, e * cap)             # drop row
    return RoutePlan(head.experts, head.gates, head.aux, order, se, st,
                     sg, keep, slot,
                     (seg_end - seg_start).astype(jnp.int32), cap)


def route(cfg: ModelConfig, p: Dict, xt: jax.Array,
          name: str = "moe") -> RoutePlan:
    """Full dispatch plan for flat tokens xt: (T, d)."""
    return plan_from_head(cfg, route_head(cfg, p, xt, name))


def reuse_plan(plan: RoutePlan, head: RouteHead) -> RoutePlan:
    """Rebind a structural plan to a fresh routing head.

    Only valid when ``head.experts`` equals ``plan.experts`` elementwise
    (the caller checks): the structure is a pure function of the expert
    ids, so the sort/positions/slots carry over bitwise while the gate
    values and aux loss come from the new head.
    """
    return plan._replace(experts=head.experts, gates=head.gates,
                         aux=head.aux,
                         sg=head.gates.reshape(-1)[plan.order])


def apply_route(plan: RoutePlan, xt: jax.Array) -> jax.Array:
    """Scatter flat tokens xt: (T, d) into the (E, C, d) expert buffer."""
    e = plan.counts.shape[0]
    cap = plan.cap
    d = xt.shape[-1]
    buf = jnp.zeros((e * cap + 1, d), xt.dtype)
    buf = buf.at[plan.slot].set(xt[plan.st].astype(xt.dtype))
    return buf[:-1].reshape(e, cap, d)


def flipped_assignments(spec: RoutePlan, true: RoutePlan) -> jax.Array:
    """(T*K,) bool mask, flat (token-major, k-minor) order: assignments
    whose dispatch *placement* differs between two plans.

    An assignment is flipped when its expert id changed OR its buffer
    slot moved — the latter catches the cascades a raw expert comparison
    misses: a flip elsewhere in a segment displaces every later position
    in it, and can push previously-kept assignments over capacity (their
    slot collapses to the drop row). Pinned against a brute-force
    placement oracle in tests/test_moe_flip.py.
    """
    def flat_slot(p: RoutePlan) -> jax.Array:
        # slot[i] belongs to sorted position i == flat index order[i]
        return jnp.zeros_like(p.slot).at[p.order].set(p.slot)

    return ((spec.experts.reshape(-1) != true.experts.reshape(-1))
            | (flat_slot(spec) != flat_slot(true)))


def dispatch(cfg: ModelConfig, p: Dict, xt: jax.Array,
             name: str = "moe") -> Dispatch:
    """Route flat tokens xt: (T, d) to the (E, C, d) expert buffer."""
    plan = route(cfg, p, xt, name)
    buf = apply_route(plan, xt)
    return Dispatch(buf, plan.slot, plan.st, plan.sg, plan.keep, plan.aux,
                    plan.counts)


def moe_ffn(cfg: ModelConfig, p: Dict, x: jax.Array,
            name: str = "moe") -> MoEOutput:
    """x: (B, S, D) -> routed expert mixture, same shape.

    When distributed rules are active, dispatch runs under a partial-manual
    ``shard_map`` (manual over the DP axes, GSPMD-auto over ``model``): the
    argsort/scatter routing then stays **local to each data shard** instead
    of forcing GSPMD to materialize the global (T·K, d) dispatch on every
    chip (measured 58 replicated full-size gathers/layer on deepseek-v3
    train_4k — §Perf cell B). Expert einsums still partition over ``model``
    (EP) inside the auto region.
    """
    from repro.distributed.sharding import current_rules
    rules = current_rules()
    if (rules is not None and rules.dp_axes
            and getattr(rules, "ep_local_dispatch", True)
            and x.shape[0] % rules.dp_size() == 0):
        from jax.sharding import PartitionSpec as P
        dp = tuple(rules.dp_axes)

        def local(xl):
            out = _moe_ffn_body(cfg, p, xl, name)
            return (out.y, jax.lax.pmean(out.aux_loss, dp),
                    jax.lax.pmean(out.expert_load, dp))

        # manual over the DP axes only; the rest of the mesh stays auto
        y, aux, load = jax.shard_map(
            local, mesh=rules.mesh,
            in_specs=(P(dp),), out_specs=(P(dp), P(), P()),
            axis_names=frozenset(dp), check_vma=False)(x)
        return MoEOutput(y, aux, load)
    return _moe_ffn_body(cfg, p, x, name)


def _moe_ffn_body(cfg: ModelConfig, p: Dict, x: jax.Array,
                  name: str = "moe") -> MoEOutput:
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.num_experts, m.top_k
    xt = x.reshape(t, d)
    cap = _capacity(cfg, t)

    dsp = dispatch(cfg, p, xt, name)
    buf, slot, st, sg, keep, aux = (dsp.buf, dsp.slot, dsp.st, dsp.sg,
                                    dsp.keep, dsp.aux)

    # --- expert FFN (stacked einsum; E sharded over model axis) ------------
    # experts may be int4-packed (quantized serving): dequantize on the fly —
    # HBM reads stay at 0.5 byte/weight, which is the memory-bound decode win
    g = jnp.einsum("ecd,edf->ecf", buf.astype(jnp.bfloat16),
                   _expert_weights(p["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", buf.astype(jnp.bfloat16),
                   _expert_weights(p["w_up"]))
    hmid = _act(cfg.act, g.astype(jnp.float32)).astype(jnp.bfloat16) * u
    yexp = jnp.einsum("ecf,efd->ecd", hmid,
                      _expert_weights(p["w_down"]))             # (E, C, d)

    # --- combine ------------------------------------------------------------
    yflat = yexp.reshape(e * cap, d)
    contrib = jnp.where(keep[:, None], yflat[jnp.clip(slot, 0, e * cap - 1)],
                        0.0).astype(jnp.float32) * sg[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[st].add(contrib)

    if m.num_shared_experts > 0:
        y = y + mlp(cfg, p["shared"], xt[None], name=f"{name}.shared"
                    )[0].astype(jnp.float32)

    load = dsp.counts.astype(jnp.float32) * e / (t * k)  # 1.0 == balanced
    return MoEOutput(y.reshape(b, s, d).astype(x.dtype), aux, load)
