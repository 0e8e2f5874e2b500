"""GSPMD sharding rules: params by path, activations by hint.

Mesh axes (launch/mesh.py): ``("data", "model")`` single-pod,
``("pod", "data", "model")`` multi-pod.

Strategy (DESIGN.md §2):
  - **DP** over ``pod`` × ``data`` — batch dims.
  - **TP** over ``model`` — Megatron col/row parallel linears, expert
    parallelism for stacked MoE weights, vocab-parallel embedding where the
    vocab divides.
  - **FSDP** over ``data`` — the non-TP weight dim (params gathered by XLA
    per layer; optimizer state stays sharded). Within-pod only: cross-pod
    param all-gathers would cross DCN every layer.
  - **SP** (optional) — sequence dim of the residual stream over ``model``.
  - **Quant-group sharding** (DESIGN.md §2.6) — the batched quantization
    executor's stacked ``(L, Cout, Cin)`` slabs: lane (member) axis over
    ``data``, Cout row tiles over ``model`` (rows are independent given the
    Cholesky factor — see gptq.py), Hessian state over the lane axis only.
    :func:`quant_group_sharding` below.

Every rule is guarded by divisibility: a dim that doesn't divide by the
mesh axis size stays unsharded (e.g. whisper's 51866 vocab, minicpm's 36
heads). This keeps every (arch × mesh) cell lowerable.

``shard_hint(x, kind)`` is a no-op unless a :class:`Rules` context is
active, so model code never depends on a mesh.
"""
from __future__ import annotations

import contextlib
import re
import threading
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ParallelConfig

_STATE = threading.local()


# ---------------------------------------------------------------------------
# Activation hints
# ---------------------------------------------------------------------------

@dataclass
class Rules:
    mesh: Mesh
    dp_axes: Tuple[str, ...]            # ("pod","data") or ("data",)
    tp_axis: Optional[str] = "model"
    sp: bool = False                    # shard seq dim of residual over TP

    def axis_size(self, name: Optional[str]) -> int:
        if name is None:
            return 1
        return int(self.mesh.shape[name])

    def dp_size(self) -> int:
        out = 1
        for a in self.dp_axes:
            out *= self.axis_size(a)
        return out


def current_rules() -> Optional[Rules]:
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    prev = getattr(_STATE, "rules", None)
    _STATE.rules = rules
    try:
        yield rules
    finally:
        _STATE.rules = prev


def _guard(spec_entry, dim: int, rules: Rules):
    """Drop a sharding axis when the dim doesn't divide it."""
    if spec_entry is None:
        return None
    axes = spec_entry if isinstance(spec_entry, tuple) else (spec_entry,)
    size = 1
    for a in axes:
        size *= rules.axis_size(a)
    if size <= 1 or dim % size != 0:
        return None
    return spec_entry


def shard_hint(x: jax.Array, kind: str) -> jax.Array:
    """Constrain activation sharding. kinds: act (B,S,D), logits (B,S,V),
    tokens (B,S), batch1 (B, ...)."""
    rules = current_rules()
    if rules is None:
        return x
    dp = tuple(rules.dp_axes) if rules.dp_axes else None
    tp = rules.tp_axis
    if kind == "act" and x.ndim == 3:
        seq = tp if rules.sp else None
        spec = P(_guard(dp, x.shape[0], rules),
                 _guard(seq, x.shape[1], rules), None)
    elif kind == "logits" and x.ndim >= 2:
        spec = P(_guard(dp, x.shape[0], rules),
                 *([None] * (x.ndim - 2)),
                 _guard(tp, x.shape[-1], rules))
    elif kind == "tokens":
        spec = P(_guard(dp, x.shape[0], rules), *([None] * (x.ndim - 1)))
    elif kind == "batch1":
        spec = P(_guard(dp, x.shape[0], rules), *([None] * (x.ndim - 1)))
    else:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter specs by path
# ---------------------------------------------------------------------------

# (regex on '/'-joined path, spec template for the *trailing* dims)
# templates use tokens: "tp" (model axis), "fsdp" (data axis), None.
_PARAM_RULES: List[Tuple[str, Tuple]] = [
    # embeddings / head
    (r"embed/embedding$",              ("tp", "fsdp")),
    (r"lm_head/w$",                    ("fsdp", "tp")),
    # attention projections (col-parallel q/k/v, row-parallel o)
    (r"(mixer|xattn)/(q|k|v)/w$",      ("fsdp", "tp")),
    (r"(mixer|xattn)/o/w$",            ("tp", "fsdp")),
    (r"(mixer|xattn)/(q|k|v)/b$",      ("tp",)),
    (r"(mixer|xattn)/o/b$",            (None,)),
    # MLA
    (r"mixer/(q_down|kv_down|k_rope)/w$", ("fsdp", None)),
    (r"mixer/(q_up|k_up|v_up)/w$",     ("fsdp", "tp")),
    # gated MLP
    (r"mlp/(gate|up)/w$",              ("fsdp", "tp")),
    (r"mlp/down/w$",                   ("tp", "fsdp")),
    (r"mlp/(gate|up)/b$",              ("tp",)),
    (r"mlp/down/b$",                   (None,)),
    # MoE (stacked experts, EP over model)
    (r"mlp/router/w$",                 (None, None)),
    (r"mlp/w_(gate|up)$",              ("tp", "fsdp", None)),
    (r"mlp/w_down$",                   ("tp", None, "fsdp")),
    (r"mlp/shared/(gate|up)/w$",       ("fsdp", "tp")),
    (r"mlp/shared/down/w$",            ("tp", "fsdp")),
    # mamba
    (r"mixer/in/w$",                   ("fsdp", "tp")),
    (r"mixer/x/w$",                    ("tp", None)),
    (r"mixer/dt/w$",                   (None, "tp")),
    (r"mixer/dt/b$",                   ("tp",)),
    (r"mixer/out/w$",                  ("tp", "fsdp")),
    (r"mixer/conv/w$",                 (None, "tp")),
    (r"mixer/conv/b$",                 ("tp",)),
    (r"mixer/a_log$",                  ("tp", None)),
    (r"mixer/d_skip$",                 ("tp",)),
    # rg-lru
    (r"mixer/(gate)/w$",               ("fsdp", "tp")),
    (r"mixer/(rg|ig)/w$",              (None, "tp")),
    (r"mixer/(rg|ig)/b$",              ("tp",)),
    (r"mixer/lambda$",                 ("tp",)),
    # mtp
    (r"mtp/proj/w$",                   ("fsdp", "tp")),
    # --- int4-packed serving leaves (QuantizedTensor children /0 /1 /2,
    # (out, in·)-major — col-parallel puts `out` on tp, row-parallel `in`) --
    (r"(mixer|xattn)/(q|k|v|q_up|k_up|v_up)/w/\d$", ("tp", "fsdp")),
    (r"(mixer|xattn)/o/w/\d$",         ("fsdp", "tp")),
    (r"mixer/(q_down|kv_down|k_rope)/w/\d$", (None, "fsdp")),
    (r"mlp/(gate|up)/w/\d$",           ("tp", "fsdp")),
    (r"mlp/down/w/\d$",                ("fsdp", "tp")),
    (r"mlp/shared/(gate|up)/w/\d$",    ("tp", "fsdp")),
    (r"mlp/shared/down/w/\d$",         ("fsdp", "tp")),
    (r"mlp/w_(gate|up|down)/\d$",      ("tp", None, "fsdp")),  # (E, out, in·)
    (r"mixer/(in|gate|rg|ig)/w/\d$",   ("tp", "fsdp")),
    (r"mixer/x/w/\d$",                 (None, "tp")),
    (r"mixer/dt/w/\d$",                ("tp", None)),
    (r"mixer/out/w/\d$",               ("fsdp", "tp")),
    (r"lm_head/w/\d$",                 ("tp", "fsdp")),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _resolve(template: Tuple, shape: Tuple[int, ...],
             rules: Rules) -> P:
    """Template applies to trailing dims; leading (stack) dims get None."""
    ndim = len(shape)
    t = template[-ndim:] if len(template) >= ndim else template
    lead = ndim - len(t)
    entries: List = [None] * lead
    for dim, tok in zip(shape[lead:], t):
        if tok == "tp":
            entries.append(_guard(rules.tp_axis, dim, rules))
        elif tok == "fsdp":
            entries.append(_guard("data", dim, rules)
                           if rules_has_fsdp(rules) else None)
        else:
            entries.append(None)
    return P(*entries)


def rules_has_fsdp(rules: Rules) -> bool:
    return getattr(rules, "fsdp", True) and "data" in rules.mesh.axis_names


def param_pspecs(params: Any, rules: Rules, fsdp: bool = True) -> Any:
    """PartitionSpec pytree matching ``params`` (arrays or SDS)."""
    rules.fsdp = fsdp  # type: ignore[attr-defined]

    def assign(path, leaf):
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        s = _path_str(path)
        for pat, template in _PARAM_RULES:
            if re.search(pat, s):
                return _resolve(template, shape, rules)
        # default: replicate small leaves; fsdp-shard big 2D+ leaves
        if fsdp and len(shape) >= 2:
            ent = [None] * len(shape)
            for i in range(len(shape) - 1, -1, -1):
                if _guard("data", shape[i], rules) is not None:
                    ent[i] = "data"
                    break
            return P(*ent)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(assign, params)


def param_shardings(params: Any, rules: Rules, fsdp: bool = True) -> Any:
    specs = param_pspecs(params, rules, fsdp)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(rules.mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def batch_shardings(batch: Any, rules: Rules) -> Any:
    dp = tuple(rules.dp_axes) if rules.dp_axes else None

    def assign(leaf):
        shape = tuple(leaf.shape)
        ent: List = [None] * len(shape)
        if shape:
            ent[0] = _guard(dp, shape[0], rules)
        return NamedSharding(rules.mesh, P(*ent))

    return jax.tree_util.tree_map(assign, batch)


# ---------------------------------------------------------------------------
# Quantization-group sharding (DESIGN.md §2.6)
#
# The batched executor (core/plan.py) stacks a group's L same-shape linears
# into (L, Cout, Cin) slabs. Quantization is embarrassingly parallel over
# both leading axes — lanes are independent linears, and rows are
# independent given the per-lane Cholesky factor (gptq.py) — so the slab
# shards lane axis over ``data`` and Cout over ``model`` with zero
# collectives in the sweep (one lane-local psum for the Σerr² diagnostic).
# The Hessian state (L, Cin, Cin) shards over the lane axis only: each
# lane's damp + Cholesky runs on the devices that hold that lane's rows,
# and the factor is replicated across the ``model`` axis its row tiles use.
# With an ``expert`` mesh axis (launch/mesh.py "DxMxE"), groups made
# entirely of stacked expert slabs shard lanes over expert (×data) instead
# — expert parallelism for the quantization executors.
# ---------------------------------------------------------------------------

_QUANT_GROUP_SPECS = {
    # kind → spec template over (lane, row, ...) tokens. Grids (scales/
    # zeros) share the "w" layout but are only ever produced inside the
    # sweep's shard_map, never placed from the host.
    "w":       ("lane", "row", None),        # (L, Cout, Cin) weight slab
    "hessian": ("lane", None, None),         # (L, Cin, Cin) Gram/damped H
    "x":       ("lane", None, None),         # (L, n_last, Cin) instance
    "lane":    ("lane",),                    # (L,) counts / err / masks
}


@dataclass(frozen=True)
class QuantGroupSharding:
    """Resolved mesh placement for one quant group's stacked slabs.

    ``lane_axis``/``row_axis`` are mesh axis names or None when the
    corresponding dim failed its divisibility guard; at least one is set
    (``quant_group_sharding`` returns None otherwise, and the executor
    keeps the group single-device). ``lane_axis`` may also be a *tuple*
    of axis names (e.g. ``("expert", "data")``) when an expert-stacked
    group shards lanes over the expert × data product — PartitionSpec
    accepts the tuple entry directly and shard_map splits the dim over
    the axes' product.
    """
    mesh: Mesh
    lane_axis: Any                      # str | tuple[str, ...] | None:
    #                                     stacked member axis → "data",
    #                                     or ("expert", ...) for
    #                                     expert-stacked groups
    row_axis: Optional[str]             # Cout row tiles → "model"

    def spec(self, kind: str) -> P:
        tokens = _QUANT_GROUP_SPECS[kind]
        return P(*(self.lane_axis if t == "lane"
                   else self.row_axis if t == "row" else None
                   for t in tokens))

    def sharding(self, kind: str) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(kind))

    def cache_key(self) -> Tuple:
        """Stable executor-cache component: mesh identity + chosen axes."""
        return (self.lane_axis, self.row_axis, self.mesh.axis_names,
                tuple(self.mesh.devices.shape),
                tuple(d.id for d in self.mesh.devices.flat))


def quant_group_sharding(mesh: Optional[Mesh], lanes: int, out_dim: int,
                         expert_stacked: bool = False
                         ) -> Optional[QuantGroupSharding]:
    """Placement for a stacked (lanes, out_dim, ·) quant group, or None.

    Divisibility guards mirror the param rules above, per axis: a lane
    axis is used only when its size divides ``lanes`` evenly, the row
    axis only when ``model`` divides ``out_dim``. A group that fails
    both guards stays unsharded (None), so every config remains
    lowerable regardless of mesh shape.

    ``expert_stacked`` marks a group made entirely of stacked expert
    slabs: when the mesh carries an ``expert`` axis, such groups offer
    their lane axis to it — preferring the combined
    ``("expert", "data")`` product, then ``expert`` alone, then the
    plain ``data`` fallback. Per-expert Hessians travel with their lane,
    so expert-axis placement adds no collectives beyond what the data
    axis already pays. Non-expert groups ignore the expert axis
    entirely.
    """
    if mesh is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get("data", 1)
    tp = sizes.get("model", 1)
    ep = sizes.get("expert", 1)

    def _axes_fit(axes: Tuple[str, ...]) -> bool:
        prod = 1
        for a in axes:
            prod *= sizes.get(a, 1)
        return prod > 1 and lanes % prod == 0

    lane_ax: Any = None
    candidates: List[Tuple[str, ...]] = []
    if expert_stacked and ep > 1:
        candidates += [("expert", "data"), ("expert",)]
    candidates.append(("data",))
    for cand in candidates:
        axes = tuple(a for a in cand if sizes.get(a, 1) > 1)
        if axes and _axes_fit(axes):
            lane_ax = axes[0] if len(axes) == 1 else axes
            break
    row_ax = "model" if tp > 1 and out_dim % tp == 0 else None
    if lane_ax is None and row_ax is None:
        return None
    return QuantGroupSharding(mesh, lane_ax, row_ax)


def make_rules(mesh: Mesh, parallel: Optional[ParallelConfig] = None
               ) -> Rules:
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    tp = "model" if "model" in names else None
    sp = bool(parallel.sp) if parallel is not None else False
    return Rules(mesh=mesh, dp_axes=dp, tp_axis=tp, sp=sp)
