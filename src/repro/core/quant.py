"""Quantization grid primitives: group-wise asymmetric low-bit quantization.

Conventions (match GPTQ / AutoGPTQ):
  - weights quantized along the *input* dimension in groups of ``group_size``
  - asymmetric: q = clip(round(w/scale) + zero, 0, 2^bits-1)
                dq = scale * (q - zero)
  - symmetric:  q = clip(round(w/scale), -2^(b-1), 2^(b-1)-1), zero = 0
  - storage packs two 4-bit values per uint8 along the input dim.

All functions are pure jnp and jit-safe. Shapes:
  W           (out, in)
  scales      (out, n_groups)      n_groups = in // group_size
  zeros       (out, n_groups)      stored as float for exact dequant math
  qweight     (out, in)  int8      unpacked codes
  packed      (out, in // 2) uint8 two nibbles per byte (low nibble = even col)
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class QuantParams(NamedTuple):
    """Group quantization parameters for one weight matrix."""
    scales: jax.Array   # (out, n_groups) float32
    zeros: jax.Array    # (out, n_groups) float32 (integer-valued)


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """A packed quantized weight matrix (the serving artifact).

    Registered pytree with static metadata aux data, so jit / eval_shape /
    device_put treat (packed, scales, zeros) as array leaves while
    (shape, bits, group_size) stay Python ints — required for the jit'd
    quantized serve path and ShapeDtypeStruct lowering.
    """

    def __init__(self, packed, scales, zeros, shape: Tuple[int, int],
                 bits: int, group_size: int):
        self.packed = packed    # (out, in//2) uint8
        self.scales = scales    # (out, n_groups)
        self.zeros = zeros      # (out, n_groups)
        self.shape = tuple(shape)
        self.bits = int(bits)
        self.group_size = int(group_size)

    def tree_flatten(self):
        return ((self.packed, self.scales, self.zeros),
                (self.shape, self.bits, self.group_size))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def __repr__(self):
        return (f"QuantizedTensor(shape={self.shape}, bits={self.bits}, "
                f"group_size={self.group_size})")


def compute_qparams(w: jax.Array, bits: int, group_size: int,
                    symmetric: bool = False) -> QuantParams:
    """Compute per-(row, group) scale/zero from weight values.

    w: (out, in). Groups tile the input dim; ``in`` must be divisible by
    group_size (configs guarantee this; pad upstream otherwise).
    """
    out_dim, in_dim = w.shape
    assert in_dim % group_size == 0, (in_dim, group_size)
    g = w.reshape(out_dim, in_dim // group_size, group_size).astype(jnp.float32)
    qmax = 2.0 ** bits - 1.0
    if symmetric:
        absmax = jnp.max(jnp.abs(g), axis=-1)
        scale = jnp.maximum(absmax / (2.0 ** (bits - 1) - 1), 1e-8)
        zero = jnp.zeros_like(scale)
    else:
        wmax = jnp.maximum(jnp.max(g, axis=-1), 0.0)
        wmin = jnp.minimum(jnp.min(g, axis=-1), 0.0)
        scale = jnp.maximum((wmax - wmin) / qmax, 1e-8)
        zero = jnp.clip(jnp.round(-wmin / scale), 0.0, qmax)
    return QuantParams(scale, zero)


def quantize_codes(w: jax.Array, qp: QuantParams, bits: int,
                   group_size: int, symmetric: bool = False) -> jax.Array:
    """Map weights to integer codes (stored as int32 for safe arithmetic)."""
    out_dim, in_dim = w.shape
    n_groups = in_dim // group_size
    scale = jnp.repeat(qp.scales, group_size, axis=1)
    zero = jnp.repeat(qp.zeros, group_size, axis=1)
    if symmetric:
        lo, hi = -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), lo, hi)
    else:
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale) + zero,
                     0.0, 2.0 ** bits - 1.0)
    return q.astype(jnp.int32)


def dequantize_codes(q: jax.Array, qp: QuantParams, group_size: int,
                     symmetric: bool = False,
                     dtype=jnp.float32) -> jax.Array:
    scale = jnp.repeat(qp.scales, group_size, axis=1)
    if symmetric:
        return (q.astype(jnp.float32) * scale).astype(dtype)
    zero = jnp.repeat(qp.zeros, group_size, axis=1)
    return ((q.astype(jnp.float32) - zero) * scale).astype(dtype)


def fake_quantize(w: jax.Array, bits: int, group_size: int,
                  symmetric: bool = False,
                  qp: QuantParams | None = None) -> jax.Array:
    """Round-trip quantize→dequantize (the ``Q(.)`` of the paper, eq. 7).

    If ``qp`` is given, the grid is fixed (RPIQ stage-2 projections onto the
    stage-1 grid); otherwise scale/zero are recomputed from ``w``.
    """
    if qp is None:
        qp = compute_qparams(w, bits, group_size, symmetric)
    q = quantize_codes(w, qp, bits, group_size, symmetric)
    return dequantize_codes(q, qp, group_size, symmetric, dtype=w.dtype)


def quantize_column(w_col: jax.Array, scale: jax.Array, zero: jax.Array,
                    bits: int, symmetric: bool = False) -> jax.Array:
    """Quantize+dequantize a single column given per-row scale/zero.

    Used inside the GPTQ column loop. w_col/scale/zero: (out,).
    """
    if symmetric:
        lo, hi = -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1
        q = jnp.clip(jnp.round(w_col / scale), lo, hi)
        return q * scale
    qmax = 2.0 ** bits - 1.0
    q = jnp.clip(jnp.round(w_col / scale) + zero, 0.0, qmax)
    return (q - zero) * scale


# ---------------------------------------------------------------------------
# Nibble packing (4-bit storage)
# ---------------------------------------------------------------------------

def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int codes in [0,15], shape (out, in), into (out, in//2) uint8.

    Low nibble holds the even column, high nibble the odd column.
    """
    out_dim, in_dim = q.shape
    assert in_dim % 2 == 0
    q = q.astype(jnp.uint8)
    lo = q[:, 0::2]
    hi = q[:, 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4` → (out, in) int32 codes."""
    lo = (packed & jnp.uint8(0x0F)).astype(jnp.int32)
    hi = ((packed >> 4) & jnp.uint8(0x0F)).astype(jnp.int32)
    out = jnp.stack([lo, hi], axis=-1)  # (out, in//2, 2)
    return out.reshape(packed.shape[0], packed.shape[1] * 2)


def pack_quantized(w: jax.Array, bits: int, group_size: int,
                   symmetric: bool = False) -> QuantizedTensor:
    """Full quantize→pack path producing the serving artifact."""
    assert bits == 4, "packed storage currently supports 4-bit"
    qp = compute_qparams(w, bits, group_size, symmetric)
    q = quantize_codes(w, qp, bits, group_size, symmetric)
    if symmetric:  # shift to unsigned storage
        q = q + 8
        zeros = qp.zeros + 8.0
    else:
        zeros = qp.zeros
    return QuantizedTensor(pack_int4(q), qp.scales, zeros,
                           tuple(w.shape), bits, group_size)


def dequantize_packed(qt: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    q = unpack_int4(qt.packed)
    qp = QuantParams(qt.scales, qt.zeros)
    return dequantize_codes(q, qp, qt.group_size, symmetric=False, dtype=dtype)


def quant_error(w: jax.Array, bits: int, group_size: int,
                symmetric: bool = False) -> jax.Array:
    """Frobenius norm of the round-to-nearest quantization error (diagnostic)."""
    return jnp.linalg.norm(w - fake_quantize(w, bits, group_size, symmetric))
