"""Dispatch layer for the Pallas kernels.

Every op has three implementations:
  - ``*_pallas``  — the TPU kernel (interpret=True on CPU for validation),
  - ``*_ref``     — the pure-jnp oracle in :mod:`repro.kernels.ref`,
  - an XLA path (== ref) used on non-TPU backends.

``impl`` selects: "auto" (pallas-interpret only when explicitly requested on
CPU; real Mosaic lowering on TPU), "pallas", "xla". The CPU container always
*validates* the kernels in interpret mode via tests; production dispatch
defaults to XLA off-TPU so jit'd steps stay fast.

Padding contracts: callers may pass any shapes; wrappers pad to tile
multiples and slice back, so kernels keep hard divisibility asserts.
"""
from __future__ import annotations

import contextlib
import math
import warnings

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.kernels import ref
from repro.kernels.gptq_block import gptq_block_pallas
from repro.kernels.rpiq_block import rpiq_block_pallas
from repro.kernels.hessian_accum import hessian_accum_pallas
from repro.kernels.selective_scan import selective_scan_pallas
from repro.kernels.w4a16_matmul import w4a16_matmul_pallas
from repro.kernels.kv_attention import int8_kv_attention_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Structured fallback accounting
#
# "auto" may resolve away from the pallas kernel because a budget guard
# (VMEM residency, HBM candidate-stack) failed. That downgrade used to be
# silent — a wide layer would quietly run the XLA body and the only
# symptom was a perf cliff. Every budget-driven downgrade now lands here:
# one warning per (op, reason) per process, plus counters that
# QuantReport.kernel_fallbacks and the serving engines' engine_stats()
# surface. Decisions happen at trace time, so a counter increments once
# per compiled entry, not once per call.
# ---------------------------------------------------------------------------

_FALLBACK_STATS: dict[str, int] = {}
_FALLBACK_WARNED: set[str] = set()
# per-caller scopes (innermost last): engine instances route their own
# fallback accounting here so two engines in one process never read each
# other's downgrades out of the module-global dict (engine_stats() would
# otherwise cross-contaminate — pinned in tests/test_supervisor.py)
_FALLBACK_SCOPES: list[dict[str, int]] = []


def fallback_stats() -> dict[str, int]:
    """Copy of the ``{"op:reason": count}`` auto→xla downgrade counters
    (process-global; per-engine views come from :func:`fallback_scope`)."""
    return dict(_FALLBACK_STATS)


def reset_fallback_stats() -> None:
    _FALLBACK_STATS.clear()
    _FALLBACK_WARNED.clear()


@contextlib.contextmanager
def fallback_scope(counters: dict[str, int]):
    """Additionally route downgrade counters into ``counters`` while the
    scope is active. Scopes nest; only the innermost receives the note —
    each engine wraps its own traces, so a downgrade is attributed to
    exactly the engine whose trace triggered it."""
    _FALLBACK_SCOPES.append(counters)
    try:
        yield counters
    finally:
        _FALLBACK_SCOPES.pop()


def _note_fallback(op: str, reason: str) -> None:
    key = f"{op}:{reason}"
    _FALLBACK_STATS[key] = _FALLBACK_STATS.get(key, 0) + 1
    if _FALLBACK_SCOPES:
        scope = _FALLBACK_SCOPES[-1]
        scope[key] = scope.get(key, 0) + 1
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        warnings.warn(
            f"kernels.ops.{op}: impl='auto' fell back to the XLA path "
            f"({reason}); force impl='pallas' to override, or retile",
            RuntimeWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# H += X^T X
# ---------------------------------------------------------------------------

# Small Hessian tiles take thousands of grid steps and re-read ``x`` for
# every output tile: at 128-edge tiles a d-3072 call ran 5-9x its MXU
# time on a v5e (PERF.md §5, §6). So the tiles are as large as the chip's
# VMEM allows: the kernel raises Mosaic's scoped limit to
# _HESSIAN_VMEM_LIMIT_BYTES and the tiles' residency stays 4 MiB under it.
_HESSIAN_MAX_EDGE = 1024
_HESSIAN_MAX_SLAB = 2048
_HESSIAN_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
_HESSIAN_VMEM_BUDGET_BYTES = _HESSIAN_VMEM_LIMIT_BYTES - 4 * 1024 * 1024


def _hessian_vmem_bytes(block_d: int, block_n: int, d: int) -> int:
    """Residency of one grid step: the double-buffered ``x`` slabs (one
    operand for the whole-square tile, two otherwise), the output tile
    (single-buffered where one tile covers the square and never moves,
    double-buffered otherwise), and Mosaic's scratch for the dot, 2 bytes
    a slab element plus 1 MiB (the compiler for a described v5e allocates
    1.7-2.2 bytes a slab element beyond the tiles)."""
    tiles = (4 * (d * d + 2 * block_n * d) if block_d == d
             else 4 * (2 * block_d * block_d + 4 * block_n * block_d))
    return tiles + 2 * block_n * block_d + 2 ** 20


def _hessian_tiles(n: int, d: int) -> tuple[int, int]:
    """``(block_d, block_n)`` for x (n, d), from the shape alone.

    The tile edge is the largest multiple of 128 that divides the
    128-rounded width and is at most ``_HESSIAN_MAX_EDGE`` (the whole
    width below 128), so no width is padded beyond 128. The token slab is
    the deepest power of two up to ``_HESSIAN_MAX_SLAB`` whose residency
    fits the budget, then evened out over the slabs ``n`` needs, so the
    token padding stays under 8 rows a slab."""
    if d < 128:
        block_d = d
    else:
        d = _round_up(d, 128)
        edges = range(128, min(d, _HESSIAN_MAX_EDGE) + 1, 128)
        block_d = max(e for e in edges if d % e == 0)
    slab = _HESSIAN_MAX_SLAB
    while slab > 8 and (_hessian_vmem_bytes(block_d, slab, d)
                        > _HESSIAN_VMEM_BUDGET_BYTES):
        slab //= 2
    steps = -(-n // slab)
    return block_d, _round_up(-(-n // steps), 8)


def hessian_accum(x: jax.Array, *, impl: str = "auto",
                  interpret: bool | None = None) -> jax.Array:
    """Gram matrix X^T X with fp32 accumulation. x: (n, d). ``interpret``
    overrides the off-TPU interpret default, as in :func:`gptq_block`.

    The kernel's tiles follow from ``(n, d)`` (:func:`_hessian_tiles`):
    square output tiles of the largest 128-multiple edge up to 1024 that
    divides the 128-rounded width (d 768 is one whole tile, read from a
    single ``x`` operand; d 3072 takes 1024-edge tiles), and token slabs as
    deep as the raised VMEM limit lets them be, up to 2048."""
    if impl == "xla" or (impl == "auto" and not _on_tpu()):
        return ref.hessian_accum_ref(x)
    n, d = x.shape
    block_d, block_n = _hessian_tiles(n, d)
    n_pad, d_pad = _round_up(n, block_n), _round_up(d, block_d)
    if (n_pad, d_pad) != (n, d):
        x = jnp.pad(x, ((0, n_pad - n), (0, d_pad - d)))
    H = hessian_accum_pallas(
        x, block_d=block_d, block_n=block_n,
        vmem_limit_bytes=_HESSIAN_VMEM_LIMIT_BYTES,
        interpret=(not _on_tpu()) if interpret is None else interpret)
    return H[:d, :d]


# ---------------------------------------------------------------------------
# y = x @ dequant(W)^T      (W packed int4, grouped scales/zeros)
# ---------------------------------------------------------------------------

# The serving engines install cfg.serve.w4a16_impl here (a trace-time
# default, read when impl is not passed explicitly): every QuantizedTensor
# dense on the decode path flows through models/linear.dense, which cannot
# thread an impl argument without widening every model signature. Callers
# that jit must key their compiled entries on the impl they installed —
# serving/engine.py and serving/scheduler.py build their jitted steps per
# engine instance with the knob fixed at construction (docs/SERVING.md).
_W4A16_DEFAULT_IMPL = "auto"


@contextlib.contextmanager
def w4a16_default_impl(impl: str):
    """Scoped override of the w4a16_matmul default backend (trace-time)."""
    global _W4A16_DEFAULT_IMPL
    assert impl in ("auto", "pallas", "xla"), impl
    prev = _W4A16_DEFAULT_IMPL
    _W4A16_DEFAULT_IMPL = impl
    try:
        yield
    finally:
        _W4A16_DEFAULT_IMPL = prev


def _w4a16_vmem_bytes(block_m: int, block_n: int, block_k: int,
                      n_groups: int) -> int:
    """Per-cell residency upper bound: double-buffered x (even + odd
    halves, f32-sized), packed u8, scale/zero (full group row, lane-padded)
    and output tiles, the f32 accumulator, and the four (bn, bk/2) f32
    unpack/dequant temporaries the kernel materializes."""
    bkh = block_k // 2
    g_lanes = _round_up(n_groups, 128)
    buffered = (4 * block_m * block_k + block_n * bkh
                + 2 * 4 * block_n * g_lanes + 4 * block_m * block_n)
    return 2 * buffered + 4 * block_m * block_n + 4 * 4 * block_n * bkh


_W4A16_MAX_BLOCK_K = 1024


def _w4a16_tiles(m: int, n: int, k: int, group_size: int):
    """The dispatcher's tiling for an (m, k) x (n, k) int4 matmul.

    Returns ``(block_m, block_n, block_k, m_pad, n_pad, k_pad)``. block_k
    is all of k when k fits one step (a full-dim block is always legal),
    else the largest multiple of ``lcm(256, group_size)`` up to
    ``_W4A16_MAX_BLOCK_K`` that divides k — so ``block_k // 2`` is a
    multiple of 128 lanes and a group never straddles tiles — with k
    padded up to that step when nothing divides.
    """
    m_pad = _round_up(max(m, 1), 8)
    block_m = 128 if m_pad >= 128 else m_pad
    m_pad = _round_up(m_pad, block_m)
    block_n = 128
    n_pad = _round_up(n, block_n)
    if k <= _W4A16_MAX_BLOCK_K:
        return block_m, block_n, k, m_pad, n_pad, k
    step = math.lcm(256, group_size)
    k_pad = _round_up(k, step)
    block_k = max(c for c in range(step, max(_W4A16_MAX_BLOCK_K, step) + 1,
                                   step) if k_pad % c == 0)
    return block_m, block_n, block_k, m_pad, n_pad, k_pad


def w4a16_matmul(x: jax.Array, packed: jax.Array, scales: jax.Array,
                 zeros: jax.Array, *, group_size: int = 128,
                 impl: str | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """x: (..., k); packed: (n, k//2) u8; scales/zeros: (n, k//group_size).

    ``interpret`` overrides the off-TPU interpret default, as in
    :func:`gptq_block` (the compile tests lower the dispatcher's own tiles
    for a described TPU with ``interpret=False``)."""
    if impl is None:
        impl = _W4A16_DEFAULT_IMPL
    if impl == "xla" or (impl == "auto" and not _on_tpu()):
        lead = x.shape[:-1]
        y = ref.w4a16_matmul_ref(x.reshape(-1, x.shape[-1]), packed,
                                 scales, zeros, group_size)
        return y.reshape(*lead, -1)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m, k = x2.shape
    n = packed.shape[0]
    block_m, block_n, block_k, m_pad, n_pad, k_pad = _w4a16_tiles(
        m, n, k, group_size)
    if (impl == "auto"
            and _w4a16_vmem_bytes(block_m, block_n, block_k,
                                  k_pad // group_size) > _VMEM_BUDGET_BYTES):
        _note_fallback("w4a16_matmul", "vmem-budget")
        y = ref.w4a16_matmul_ref(x2, packed, scales, zeros, group_size)
        return y.reshape(*lead, -1)
    # fault site: an injected Mosaic/lowering failure at the moment the
    # fused kernel would be traced — drives the serving engines' runtime
    # pallas→xla degradation path (docs/SERVING.md §Failure handling)
    faults.fire("kernels.pallas_dispatch")
    if (m_pad, k_pad) != (m, k):
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, k_pad - k)))
    if (n_pad, k_pad) != (n, k):
        # padded columns meet zero activations and padded rows are sliced
        # off, so the (s=1, z=0) filler never reaches a real output
        g_pad = (k_pad - k) // group_size
        packed = jnp.pad(packed, ((0, n_pad - n), (0, (k_pad - k) // 2)))
        scales = jnp.pad(scales, ((0, n_pad - n), (0, g_pad)),
                         constant_values=1.0)
        zeros = jnp.pad(zeros, ((0, n_pad - n), (0, g_pad)))
    y = w4a16_matmul_pallas(
        x2, packed, scales, zeros, group_size=group_size, block_m=block_m,
        block_n=block_n, block_k=block_k,
        interpret=(not _on_tpu()) if interpret is None else interpret)
    return y[:m, :n].reshape(*lead, n)


# ---------------------------------------------------------------------------
# decode attention against an int8 KV cache (fused dequant)
# ---------------------------------------------------------------------------

# Same contract as _W4A16_DEFAULT_IMPL: the serving engines install
# cfg.serve.kv_impl here at trace time, because attention_decode sits under
# the jitted decode step and cannot thread an impl argument without
# widening every model signature. Engines key compiled entries on the
# installed impl (docs/SERVING.md).
_KV_ATTN_DEFAULT_IMPL = "auto"


@contextlib.contextmanager
def kv_attn_default_impl(impl: str):
    """Scoped override of the int8_kv_attention default backend."""
    global _KV_ATTN_DEFAULT_IMPL
    assert impl in ("auto", "pallas", "xla"), impl
    prev = _KV_ATTN_DEFAULT_IMPL
    _KV_ATTN_DEFAULT_IMPL = impl
    try:
        yield
    finally:
        _KV_ATTN_DEFAULT_IMPL = prev


def _kv_attn_vmem_bytes(block_s: int, kv: int, r: int, hd: int,
                        nb: int) -> int:
    """Per-cell residency with Mosaic's tile padding: double-buffered
    int8 K/V code tiles ((kv, hd) padded to (32, 128)), f32 scale tiles
    ((kv, nb) padded to (8, 128)), q/out tiles and the kpos row; the
    acc/m/l scratch; and one head's f32 temporaries (two dequantized
    (bs, hd) tiles, scores and probabilities)."""
    hd_l = _round_up(hd, 128)
    codes = block_s * _round_up(kv, 32) * hd_l
    scales = 4 * block_s * _round_up(kv, 8) * _round_up(nb, 128)
    qo = 4 * kv * _round_up(r, 8) * hd_l
    buffered = 2 * codes + 2 * scales + 2 * qo + 4 * 8 * block_s
    scratch = 4 * kv * _round_up(r, 8) * (hd_l + 2 * 128)
    temps = 4 * (2 * block_s * hd_l + 2 * _round_up(r, 8) * block_s)
    return 2 * buffered + scratch + temps


def int8_kv_attention(q: jax.Array, k_codes: jax.Array, k_scales: jax.Array,
                      v_codes: jax.Array, v_scales: jax.Array,
                      kpos: jax.Array, *, kv_block: int,
                      softcap: float = 0.0,
                      impl: str | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """One-token GQA decode against an int8 KV cache (kernels/kv_codec.py).

    q: (B, KV, R, hd) pre-scaled queries; k/v codes: (B, S, KV, hd) int8;
    k/v scales: (B, S, KV, hd//kv_block) f32; kpos: (B, S) int32 slot
    positions, -1 = invalid (causal/window validity is encoded by the
    caller). Returns (B, KV, R, hd) in q.dtype. ``interpret`` overrides
    the off-TPU interpret default, as in :func:`w4a16_matmul`.
    """
    if impl is None:
        impl = _KV_ATTN_DEFAULT_IMPL
    assert impl in ("auto", "pallas", "xla"), impl
    if impl == "xla" or (impl == "auto" and not _on_tpu()):
        return ref.int8_kv_attention_ref(q, k_codes, k_scales, v_codes,
                                         v_scales, kpos, kv_block, softcap)
    b, s, kv, hd = k_codes.shape
    r = q.shape[2]
    nb = hd // kv_block
    # kpos rides along lanes, so a history tile is 128 slots or all of S
    block_s = 128 if s >= 128 else _round_up(s, 8)
    if (impl == "auto" and _kv_attn_vmem_bytes(block_s, kv, r, hd, nb)
            > _VMEM_BUDGET_BYTES):
        _note_fallback("int8_kv_attention", "vmem-budget")
        return ref.int8_kv_attention_ref(q, k_codes, k_scales, v_codes,
                                         v_scales, kpos, kv_block, softcap)
    # fault site shared with w4a16_matmul: an injected lowering failure at
    # the moment the fused kernel would be traced drives the engines'
    # pallas→xla degradation path (docs/SERVING.md §Failure handling)
    faults.fire("kernels.pallas_dispatch")
    s_pad = _round_up(s, block_s)
    if s_pad != s:
        k_codes = jnp.pad(k_codes, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        v_codes = jnp.pad(v_codes, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        k_scales = jnp.pad(k_scales,
                           ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        v_scales = jnp.pad(v_scales,
                           ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, s_pad - s)), constant_values=-1)
    r_pad = _round_up(r, 8)
    if r_pad != r:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))
    y = int8_kv_attention_pallas(q, k_codes, k_scales, v_codes, v_scales,
                                 kpos, kv_block=kv_block, softcap=softcap,
                                 block_s=block_s,
                                 interpret=((not _on_tpu()) if interpret
                                            is None else interpret))
    return y[:, :, :r]


# ---------------------------------------------------------------------------
# GPTQ lazy-block sweep (stage-1 quantization hot path)
# ---------------------------------------------------------------------------

# Mosaic's default scoped-VMEM limit is 16 MiB per kernel (the v5e compiler
# reports "limit 16.00M" when a kernel exceeds it); the budget keeps 4 MiB
# of headroom under it. The per-kernel estimates below count every blocked
# operand twice (the pipeline double-buffers them, unless its BlockSpec asks
# for one buffer) and are upper bounds of what the compiler allocates at
# opt-proxy's widths (tests/test_tpu_compile.py compiles the dispatcher's
# own tiles).
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def _gptq_vmem_bytes(block_out: int, in_dim: int, blocksize: int,
                     group_size: int) -> int:
    """Per-cell residency of the streamed sweep: the w-in row tile (read
    once, single-buffered), the double-buffered w-out tile and ``U`` row
    slab, lane-padded scale/zero/err outputs, the tail update's
    temporaries (the masked slab and the rank-bs product), and 1 MiB for
    Mosaic's internal scratch and the column loop's carries (the v5e
    compiler allocates 1.06-1.25 MiB beyond the tiles at every width)."""
    g_lanes = _round_up(in_dim // group_size, 128)
    return 4 * (4 * block_out * in_dim + 3 * blocksize * in_dim
                + 4 * block_out * g_lanes + 2 * block_out * 128) + 2 ** 20


def _gptq_block_out(out_dim: int, in_dim: int, blocksize: int,
                    group_size: int, block_out: int | None = None
                    ) -> tuple[int, bool]:
    """The sweep's row tile (``block_out``, by default 128 rows or the rows
    rounded up to 8) and whether its residency fits the VMEM budget."""
    bo = block_out or min(128, _round_up(out_dim, 8))
    return bo, (_gptq_vmem_bytes(bo, in_dim, blocksize, group_size)
                <= _VMEM_BUDGET_BYTES)


def gptq_block(w: jax.Array, hinv_u: jax.Array, *, bits: int = 4,
               group_size: int = 128, blocksize: int = 128,
               symmetric: bool = False, impl: str = "auto",
               block_out: int = 0, interpret: bool | None = None,
               local: bool = False):
    """One full GPTQ lazy-block sweep; the quantize-stage dispatcher.

    w: (out, in) or stacked (B, out, in); hinv_u matches with (in, in)
    trailing dims.  Returns ``(w_q, scales, zeros, err)`` shaped like the
    inputs (err: scalar per member).

    ``impl``: "pallas" forces the fused kernel (interpret-mode off-TPU),
    "xla" the ``fori_loop``-of-``dynamic_slice`` reference body in
    :mod:`repro.core.gptq`, and "auto" picks pallas on TPU when the row
    tile's per-cell VMEM residency fits the budget.  The kernel streams
    ``U`` one ``(blocksize, Cin)`` lazy-block slab at a time, so a cell
    holds ~``4·Cin·(4·block_out + 3·blocksize)`` bytes + 1 MiB: linear in
    Cin, 11.9 MiB at Cin = 3072 with 128-row tiles.  Wider inputs fall
    back to XLA, counted as ``gptq_block:vmem-budget``.
    ``interpret`` overrides the off-TPU interpret default (the TPU-export
    path in benchmarks passes ``interpret=False`` to count the kernel as
    the single XLA op it is on hardware).

    ``local=True`` marks a per-shard call under :func:`gptq_block_sharded`'s
    ``shard_map``: the operands are device-local slabs, so "auto" skips the
    multi-device guard below and may lower the pallas kernel per shard.
    """
    squeeze = w.ndim == 2
    if squeeze:
        w, hinv_u = w[None], hinv_u[None]
    assert w.ndim == 3 and hinv_u.ndim == 3, (w.shape, hinv_u.shape)
    out_dim, in_dim = w.shape[-2:]
    assert in_dim % blocksize == 0 and blocksize % group_size == 0, \
        (w.shape, blocksize, group_size)
    bo, fits = _gptq_block_out(out_dim, in_dim, blocksize, group_size,
                               block_out)
    # Outside shard_map, "auto" stays on XLA in multi-device processes: the
    # documented GSPMD row-sharded path (gptq.py docstring, examples/
    # distributed_quantize.py) relies on XLA partitioning the pure-XLA
    # sweep exactly, and a bare pallas_call carries no sharding rule.  The
    # sharded executor instead calls back in through gptq_block_sharded,
    # whose shard_map hands every device its own (member, Cout-tile) slab —
    # there ``local=True`` and "auto" may pick pallas per shard
    # (DESIGN.md §2.6).  Force impl="pallas" to override by hand.
    use_pallas = impl == "pallas"
    if (impl == "auto" and _on_tpu()
            and (local or jax.device_count() == 1)):
        if fits:
            use_pallas = True
        else:
            _note_fallback("gptq_block", "vmem-budget")
    if not use_pallas:
        from repro.core.gptq import _gptq_xla_batched
        res = _gptq_xla_batched(w, hinv_u, bits=bits, group_size=group_size,
                                blocksize=blocksize, symmetric=symmetric)
        out = (res.w_q, res.scales, res.zeros, res.err)
    else:
        out_pad = _round_up(out_dim, bo)
        if out_pad != out_dim:
            w = jnp.pad(w, ((0, 0), (0, out_pad - out_dim), (0, 0)))
        w_q, scales, zeros, err_rows = gptq_block_pallas(
            w, hinv_u, bits=bits, group_size=group_size,
            blocksize=blocksize, block_out=bo, symmetric=symmetric,
            interpret=(not _on_tpu()) if interpret is None else interpret)
        out = (w_q[:, :out_dim], scales[:, :out_dim], zeros[:, :out_dim],
               jnp.sum(err_rows[:, :out_dim, 0], axis=-1))
    if squeeze:
        out = tuple(o[0] for o in out)
    return out


def _axes_prod(mesh, axis) -> int:
    """Device count along a lane placement: str, tuple of axis names
    (expert-stacked groups shard lanes over e.g. ("expert", "data") —
    distributed/sharding.quant_group_sharding), or None → 1."""
    if axis is None:
        return 1
    axes = axis if isinstance(axis, tuple) else (axis,)
    out = 1
    for a in axes:
        out *= int(mesh.shape[a])
    return out


def gptq_block_sharded(w: jax.Array, hinv_u: jax.Array, *, mesh,
                       lane_axis=None, row_axis: str | None = None,
                       bits: int = 4, group_size: int = 128,
                       blocksize: int = 128, symmetric: bool = False,
                       impl: str = "auto", interpret: bool | None = None):
    """Mesh-sharded GPTQ sweep: one device-local :func:`gptq_block` per shard.

    w: (B, out, in) stacked group slab; hinv_u: (B, in, in).  The slab is
    laid out ``P(lane_axis, row_axis, None)`` with the Cholesky factors
    ``P(lane_axis, None, None)`` — ``lane_axis`` may be a tuple of mesh
    axes (expert-stacked groups shard lanes over the ``("expert",
    "data")`` product); the kernel's (member, Cout-tile) grid is
    exactly the per-shard unit, so each device sweeps its own
    ``(B/|lane|, out/|row|, in)`` slab with no communication; the only
    collective is one psum folding the per-shard Σerr² diagnostics over the
    row axis.  Exact, not approximate: lanes are independent linears and
    rows are independent given U (gptq.py).  Divisibility over the mesh
    axes is the caller's contract (``distributed.sharding.
    quant_group_sharding`` guards it); either axis may be None to shard
    one dim only.  Under ``local=True`` dispatch, "auto" may lower the
    fused pallas kernel per shard on TPU.
    """
    from jax.sharding import PartitionSpec as P

    if lane_axis is None and row_axis is None:
        return gptq_block(w, hinv_u, bits=bits, group_size=group_size,
                          blocksize=blocksize, symmetric=symmetric,
                          impl=impl, interpret=interpret)

    def local_sweep(wl, ul):
        w_q, scales, zeros, err = gptq_block(
            wl, ul, bits=bits, group_size=group_size, blocksize=blocksize,
            symmetric=symmetric, impl=impl, interpret=interpret, local=True)
        if row_axis is not None:
            err = jax.lax.psum(err, row_axis)
        return w_q, scales, zeros, err

    slab = P(lane_axis, row_axis, None)
    return jax.shard_map(
        local_sweep, mesh=mesh,
        in_specs=(slab, P(lane_axis, None, None)),
        out_specs=(slab, slab, slab, P(lane_axis)),
        check_vma=False)(w, hinv_u)


# ---------------------------------------------------------------------------
# RPIQ closed-loop refinement (stage-2 hot path)
# ---------------------------------------------------------------------------


def _rpiq_vmem_bytes(block_out: int, in_dim: int, n: int,
                     block_size: int) -> int:
    """Per-cell residency, every blocked operand double-buffered: five
    (block_out, in) tiles (W₀, working W, round candidate, expanded
    scales/zeros) + the (n, in) instance slab + two (n, block_out) output
    slabs + the (in, bs) inverse stack."""
    return 2 * 4 * (5 * block_out * in_dim + n * in_dim
                    + 2 * n * block_out + block_size * in_dim)


_RPIQ_HBM_BUDGET_BYTES = 2 * 1024 ** 3   # per-dispatch candidate-stack cap


def _rpiq_hbm_bytes(b: int, out_pad: int, in_dim: int, t_max: int) -> int:
    """HBM footprint of the deferred-bookkeeping candidate stack: the
    kernel materializes all t_max+1 per-round projections (B, t_max+1,
    out, in) — an O(t_max) inflation the XLA body does not have, so
    "auto" must budget it separately from VMEM."""
    return 4 * b * (t_max + 1) * out_pad * in_dim


def _rpiq_select(hist_raw: jax.Array, pls_raw: jax.Array,
                 wp_all: jax.Array, t_max: int, early_stop: bool):
    """Deferred closed-loop bookkeeping over the raw round trajectory.

    Replays :func:`repro.core.rpiq._rpiq_core`'s while-loop semantics from
    the (B, t_max+1) raw Γ / projected-loss sums: round 1 always runs,
    round r+1 runs iff round r did not trip the stop predicate
    ``Γ^(r) >= Γ^(r-1)·(1-1e-6)``; non-executed rounds mask to +inf in the
    history; the returned candidate is the FIRST executed round achieving
    the minimum projected loss (strict-improvement semantics — index 0 is
    the stage-1 solution itself, so "no round improved" selects it).
    """
    b = hist_raw.shape[0]
    if early_stop:
        stop = hist_raw[:, 1:] >= hist_raw[:, :-1] * (1.0 - 1e-6)  # (B, T)
    else:
        stop = jnp.zeros((b, t_max), bool)
    live = jnp.cumprod(jnp.logical_not(stop).astype(jnp.int32), axis=1)
    exec_mask = jnp.concatenate(
        [jnp.ones((b, 1), jnp.int32), live[:, :-1]], axis=1).astype(bool)
    iters = jnp.sum(exec_mask, axis=1).astype(jnp.int32)
    keep = jnp.concatenate([jnp.ones((b, 1), bool), exec_mask], axis=1)
    hist = jnp.where(keep, hist_raw, jnp.inf)
    cand = jnp.where(keep, pls_raw, jnp.inf)
    best = jnp.argmin(cand, axis=1)              # first occurrence of min
    proj_loss = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]
    w_q = jnp.take_along_axis(wp_all, best[:, None, None, None],
                              axis=1)[:, 0]
    return w_q, hist, proj_loss, iters


def rpiq_block(w_init: jax.Array, w_fp: jax.Array, x_last: jax.Array,
               hinv_blocks: jax.Array, scales: jax.Array, zeros: jax.Array,
               *, bits: int = 4, group_size: int = 128,
               block_size: int = 128, alpha: float = 0.01, t_max: int = 5,
               early_stop: bool = True, symmetric: bool = False,
               impl: str = "auto", block_out: int = 0,
               interpret: bool | None = None, local: bool = False,
               loss_psum_axis: str | None = None):
    """The full stage-2 closed loop; the refinement-stage dispatcher.

    w_init/w_fp: (out, in) or stacked (B, out, in); x_last matches with
    (n, in) trailing dims, hinv_blocks with (M, bs, bs) — the explicit
    blockwise curvature inverses from
    :func:`repro.core.rpiq._block_curvature_inv` (shared by both
    backends, so eq. 13–14 rounds identically).  Returns the RPIQResult
    tuple ``(w_q, w_cont, loss_history, proj_loss, iters_run)`` shaped
    like the inputs.

    ``impl``: "pallas" forces the fused kernel (interpret-mode off-TPU),
    "xla" the ``while_loop``-of-``fori_loop`` reference body in
    :mod:`repro.core.rpiq`, and "auto" picks pallas on TPU only when the
    per-cell VMEM residency fits the budget — wide layers fall back to
    XLA instead of failing in Mosaic.  ``t_max == 0`` always takes the
    XLA body (the closed loop is empty; nothing to fuse).  ``interpret``
    overrides the off-TPU interpret default (the TPU-export path in
    benchmarks passes ``interpret=False``).

    ``local=True`` marks a per-shard call under
    :func:`rpiq_block_sharded`'s ``shard_map`` (same contract as
    ``gptq_block``); ``loss_psum_axis`` names the mesh axis to fold the
    per-shard Γ/projected-loss partials over BEFORE the deferred
    early-stop/best bookkeeping — the row-sharded twin's one collective.
    """
    squeeze = w_init.ndim == 2
    if squeeze:
        w_init, w_fp, x_last, hinv_blocks, scales, zeros = (
            a[None] for a in (w_init, w_fp, x_last, hinv_blocks, scales,
                              zeros))
    assert w_init.ndim == 3 and hinv_blocks.ndim == 4, \
        (w_init.shape, hinv_blocks.shape)
    b, out_dim, in_dim = w_init.shape
    n = x_last.shape[-2]
    assert in_dim % block_size == 0 and block_size % group_size == 0, \
        (w_init.shape, block_size, group_size)
    bo = block_out or (128 if out_dim >= 128 else _round_up(out_dim, 8))
    # Same multi-device guard as gptq_block: outside shard_map, "auto"
    # stays on XLA in multi-device processes (GSPMD partitions the pure-XLA
    # loop exactly; a bare pallas_call carries no sharding rule) — the
    # sharded executor calls back in through rpiq_block_sharded instead.
    use_pallas = t_max >= 1 and impl == "pallas"
    if (t_max >= 1 and impl == "auto" and _on_tpu()
            and (local or jax.device_count() == 1)):
        if _rpiq_vmem_bytes(bo, in_dim, n, block_size) > _VMEM_BUDGET_BYTES:
            _note_fallback("rpiq_block", "vmem-budget")
        elif (_rpiq_hbm_bytes(b, _round_up(out_dim, bo), in_dim, t_max)
              > _RPIQ_HBM_BUDGET_BYTES):
            _note_fallback("rpiq_block", "hbm-budget")
        else:
            use_pallas = True
    if not use_pallas:
        if loss_psum_axis is not None:
            # only reachable when a sharded caller forced impl="xla" with
            # rows still split — the twin prevents this (it gathers rows
            # for XLA-resolved backends), but keep the seam total
            raise ValueError("loss_psum_axis requires the pallas backend: "
                             "the XLA body early-stops on per-lane "
                             "data-dependent trip counts, which cannot "
                             "psum in lockstep across row shards")
        from repro.core.rpiq import _rpiq_xla_batched
        res = _rpiq_xla_batched(w_init, w_fp, x_last, hinv_blocks, scales,
                                zeros, bits=bits, group_size=group_size,
                                block_size=block_size, alpha=alpha,
                                t_max=t_max, early_stop=early_stop,
                                symmetric=symmetric)
        out = tuple(res)
    else:
        xf = x_last.astype(jnp.float32)
        # Y_orig = X W_fp^T once per member (the single-instance reference)
        y_orig = jnp.einsum("bni,boi->bno", xf, w_fp.astype(jnp.float32))
        # grid expanded to column resolution ONCE (hoisted jnp.repeat)
        s_full = jnp.repeat(scales.astype(jnp.float32), group_size, axis=-1)
        z_full = jnp.repeat(zeros.astype(jnp.float32), group_size, axis=-1)
        w0 = w_init.astype(jnp.float32)
        out_pad = _round_up(out_dim, bo)
        if out_pad != out_dim:
            # padded rows: w=0 on a (s=1, z=0) grid — projections and
            # residual contributions stay exactly 0, so real rows and the
            # Γ partial sums are unperturbed
            pad = ((0, 0), (0, out_pad - out_dim), (0, 0))
            w0 = jnp.pad(w0, pad)
            s_full = jnp.pad(s_full, pad, constant_values=1.0)
            z_full = jnp.pad(z_full, pad)
            y_orig = jnp.pad(y_orig, ((0, 0), (0, 0),
                                      (0, out_pad - out_dim)))
        hinv_flat = hinv_blocks.astype(jnp.float32).reshape(
            b, in_dim, block_size)
        w_cont, wp_all, _y_q, hist_raw, pls_raw = rpiq_block_pallas(
            w0, y_orig, xf, hinv_flat, s_full, z_full, bits=bits,
            group_size=group_size, block_size=block_size, alpha=alpha,
            t_max=t_max, symmetric=symmetric, block_out=bo,
            interpret=(not _on_tpu()) if interpret is None else interpret)
        hist_raw, pls_raw = hist_raw[:, 0], pls_raw[:, 0]
        if loss_psum_axis is not None:
            # fold row-shard partials into the global Γ trajectory — every
            # shard then replays identical bookkeeping for its rows
            hist_raw = jax.lax.psum(hist_raw, loss_psum_axis)
            pls_raw = jax.lax.psum(pls_raw, loss_psum_axis)
        w_q, hist, proj_loss, iters = _rpiq_select(hist_raw, pls_raw,
                                                   wp_all, t_max,
                                                   early_stop)
        out = (w_q[:, :out_dim], w_cont[:, :out_dim], hist, proj_loss,
               iters)
    if squeeze:
        out = tuple(o[0] for o in out)
    return out


def rpiq_block_sharded(w_init: jax.Array, w_fp: jax.Array,
                       x_last: jax.Array, h_damped: jax.Array,
                       scales: jax.Array, zeros: jax.Array, *,
                       h_count: jax.Array | None = None,
                       x_count: jax.Array | None = None, mesh=None,
                       lane_axis=None,
                       row_axis: str | None = None, bits: int = 4,
                       group_size: int = 128, block_size: int = 128,
                       alpha: float = 0.01, t_max: int = 5,
                       early_stop: bool = True, symmetric: bool = False,
                       exact_gram: bool = False, impl: str = "auto",
                       interpret: bool | None = None):
    """Mesh-sharded stage-2 refinement: the :func:`gptq_block_sharded` twin.

    w_init/w_fp: (B, out, in) stacked group slabs; h_damped: (B, in, in);
    scales/zeros: (B, out, groups).  Lanes lay out over ``lane_axis``
    exactly like stage 1 (members are independent linears, zero
    collectives).  Rows differ from the GPTQ sweep: the closed loop's Γ,
    early stop and best-projection choice are global over Cout, so a row
    shard is NOT an independent unit —

      - with the fused kernel the rounds run unconditionally and the
        bookkeeping is deferred (rpiq_block), so row sharding stays exact
        at the cost of ONE psum of the (B, t_max+1) loss partials per
        stage dispatch (``loss_psum_axis``);
      - the XLA body's while-loop trip count is data-dependent per lane —
        a mid-loop psum would have shards disagree on trip counts — so
        when the per-shard dispatch resolves to XLA the twin drops the
        row axis (the shard_map in_specs then gather rows) and shards
        lanes only.

    The blockwise curvature pre-factor runs lane-local inside the
    shard_map (each lane's Cholesky where its rows run, replicated over
    the row axis like the stage-1 factor — DESIGN.md §2.6).  Either axis
    may be None; both None degrades to the single-device dispatcher.
    """
    from jax.sharding import PartitionSpec as P
    from repro.core.rpiq import rpiq_refine_batched

    kw = dict(bits=bits, group_size=group_size, block_size=block_size,
              alpha=alpha, t_max=t_max, early_stop=early_stop,
              symmetric=symmetric, exact_gram=exact_gram)
    b, out_dim, in_dim = w_init.shape
    n = x_last.shape[-2]
    if row_axis is not None:
        rows_local = out_dim // int(mesh.shape[row_axis])
        lanes_local = b // _axes_prod(mesh, lane_axis)
        bo = 128 if rows_local >= 128 else _round_up(max(rows_local, 1), 8)
        pallas_local = t_max >= 1 and impl == "pallas"
        if t_max >= 1 and impl == "auto" and _on_tpu():
            if (_rpiq_vmem_bytes(bo, in_dim, n, block_size)
                    <= _VMEM_BUDGET_BYTES
                    and _rpiq_hbm_bytes(lanes_local,
                                        _round_up(rows_local, bo),
                                        in_dim, t_max)
                    <= _RPIQ_HBM_BUDGET_BYTES):
                pallas_local = True
            else:
                # budget-rejected per-shard kernel: the twin must also give
                # up ROW sharding (the XLA body cannot psum mid-loop), so
                # this downgrade costs layout, not just backend — record it
                _note_fallback("rpiq_block_sharded", "row-axis-dropped")
        if not pallas_local:
            row_axis = None
    if lane_axis is None and row_axis is None:
        return tuple(rpiq_refine_batched(
            w_init, w_fp, x_last, h_damped, scales, zeros, h_count=h_count,
            x_count=x_count, impl=impl, interpret=interpret, **kw))

    slab = P(lane_axis, row_axis, None)
    lane3 = P(lane_axis, None, None)
    in_specs = [slab, slab, lane3, lane3, slab, slab]
    args = [w_init, w_fp, x_last, h_damped, scales, zeros]
    if h_count is not None:
        in_specs.append(P(lane_axis))
        args.append(h_count)
    if x_count is not None:
        in_specs.append(P(lane_axis))
        args.append(x_count)

    def local_refine(*a):
        wl, wfl, xl, hdl, sl, zl = a[:6]
        rest = list(a[6:])
        hcl = rest.pop(0) if h_count is not None else None
        xcl = rest.pop(0) if x_count is not None else None
        return tuple(rpiq_refine_batched(
            wl, wfl, xl, hdl, sl, zl, h_count=hcl, x_count=xcl, impl=impl,
            interpret=interpret, local=True, loss_psum_axis=row_axis, **kw))

    # loss history / proj_loss / iters are identical across row shards
    # after the psum fold — lane-sharded only (check_vma off, as in the
    # stage-1 twin)
    out_specs = (slab, slab, P(lane_axis, None), P(lane_axis),
                 P(lane_axis))
    return jax.shard_map(local_refine, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Mamba-1 selective scan
# ---------------------------------------------------------------------------

def selective_scan(u, dt, bm, cm, a_log, d_skip, h0, *, impl: str = "auto",
                   chunk: int = 256):
    """Diagonal SSM scan. See kernels/selective_scan.py for shapes.

    XLA fallback = chunked associative scan (materializes (B, chunk, d, n)
    per chunk); pallas path keeps the state in VMEM (O(B·S·d) HBM
    traffic).
    """
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        B, S, d = u.shape
        bt = min(128, S)
        s_pad = _round_up(S, bt)
        if s_pad != S:
            padw = ((0, 0), (0, s_pad - S), (0, 0))
            u = jnp.pad(u, padw)
            dt = jnp.pad(dt, padw)
            bm = jnp.pad(bm, ((0, 0), (0, s_pad - S), (0, 0)))
            cm = jnp.pad(cm, ((0, 0), (0, s_pad - S), (0, 0)))
        y, h_last = selective_scan_pallas(u, dt, bm, cm, a_log, d_skip, h0,
                                          block_d=min(256, d), block_t=bt,
                                          interpret=not _on_tpu())
        # h_last after padded steps: padded dt=0 ⇒ a=1, b=0 ⇒ h unchanged
        return y[:, :S], h_last
    # XLA fallback: chunked diagonal recurrence (baseline memory behavior)
    from repro.models.recurrent import _chunked_recurrence
    A = -jnp.exp(a_log.astype(jnp.float32))
    a = jnp.exp(dt.astype(jnp.float32)[..., None] * A[None, None])
    b = (dt.astype(jnp.float32) * u.astype(jnp.float32))[..., None] \
        * bm.astype(jnp.float32)[:, :, None, :]
    h, h_last = _chunked_recurrence(a, b, h0.astype(jnp.float32), chunk)
    y = jnp.einsum("bsdn,bsn->bsd", h, cm.astype(jnp.float32))
    y = y + u.astype(jnp.float32) * d_skip.astype(jnp.float32)
    return y.astype(u.dtype), h_last.astype(h0.dtype)


__all__ = ["hessian_accum", "w4a16_matmul", "w4a16_default_impl",
           "int8_kv_attention", "kv_attn_default_impl",
           "gptq_block", "gptq_block_sharded", "rpiq_block",
           "rpiq_block_sharded", "selective_scan", "fallback_stats",
           "reset_fallback_stats"]
