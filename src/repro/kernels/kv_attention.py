"""Pallas TPU kernel: fused int8-KV dequant + decode attention.

The sequel to ``w4a16_matmul`` on the serving hot path: one-token GQA
decode against an int8-quantized KV cache (``kernels/kv_codec.py`` blocked
layout). The XLA reference dequantizes the whole cache to f32 before the
score/value einsums — an HBM materialization of the full history per layer
per step. This kernel instead streams (bs, hd) int8 tiles of K/V history
into VMEM, dequantizes in VREGs (broadcasted per-block scale multiply, the
``w4a16`` move), and folds them into a flash-decode online softmax — so
int8 history never exists as a full fp16/f32 tensor in HBM:

  - grid (B, S/bs) with the history axis innermost (sequential
    accumulation per batch row); each cell holds a (bs, KV, hd) tile —
    every kv head of the slot range, so the block's two minor dims are
    the array's own (Mosaic's tiling rule holds for any head count and
    head dim) — and loops over the heads statically;
  - per-head running max ``m`` / denominator ``l`` / accumulator ``acc``
    live in VMEM scratch across history tiles (m/l replicated over a 128-lane
    minor dim for TPU vector geometry);
  - invalid slots (kpos < 0: unwritten ring positions, padding) are masked
    to -1e30 *and* re-zeroed post-exp — a fully-masked tile otherwise
    contributes exp(-1e30 - (-1e30)) = 1 per slot;
  - queries arrive pre-scaled (hd^-0.5 folded in by the caller, matching
    ``attention_decode``'s fp16 path); softcap applies before masking.

Validated in interpret mode on CPU against ``ref.int8_kv_attention_ref``
and compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_S = 128
_MIN_LANES = 128                      # f32 minor-dim tile for m/l scratch


def _expand_scales(sc: jax.Array, hd: int, kv_block: int) -> jax.Array:
    """(bs, nb) per-block scales → a (bs, hd)-broadcastable factor. One
    block per head row (nb == 1, the common case) is a lane broadcast;
    otherwise each block's column is picked by a one-hot lane reduction
    (exact: a single nonzero per sum) and selected over its lanes."""
    bs, nb = sc.shape
    if nb == 1:
        return sc
    b_ids = jax.lax.broadcasted_iota(jnp.int32, (bs, nb), 1)
    lane_blk = jax.lax.broadcasted_iota(jnp.int32, (bs, hd), 1) // kv_block
    out = jnp.zeros((bs, hd), jnp.float32)
    for blk in range(nb):
        col = jnp.sum(jnp.where(b_ids == blk, sc, 0.0), axis=1,
                      keepdims=True)
        out = jnp.where(lane_blk == blk, col, out)
    return out


def _kv_attn_kernel(q_ref, kc_ref, ks_ref, vc_ref, vs_ref, kpos_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, kv_block: int, softcap: float,
                    n_s_steps: int, out_dtype):
    si = pl.program_id(1)
    n_kv, hd = kc_ref.shape[2], kc_ref.shape[3]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = kpos_ref[0] >= 0                                # (1, bs)
    for h in range(n_kv):                                   # static heads
        q = q_ref[0, h].astype(jnp.float32)                 # (R, hd)
        kc = kc_ref[0, :, h, :].astype(jnp.float32)         # (bs, hd)
        k = kc * _expand_scales(ks_ref[0, :, h, :].astype(jnp.float32),
                                hd, kv_block)               # dequant in VREGs
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),  # q @ k.T
                                preferred_element_type=jnp.float32)  # (R, bs)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, -1e30)

        m_prev = m_ref[h]                                   # (R, 128)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)                     # (R, 128)
        p = jnp.exp(s - m_cur[:, :1])                       # (R, bs)
        # fully-masked slots: exp(-1e30 - m) is 1 when m is still -1e30
        p = jnp.where(valid, p, 0.0)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_cur

        vc = vc_ref[0, :, h, :].astype(jnp.float32)         # (bs, hd)
        v = vc * _expand_scales(vs_ref[0, :, h, :].astype(jnp.float32),
                                hd, kv_block)
        acc_ref[h] = acc_ref[h] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),                 # p @ v
            preferred_element_type=jnp.float32)

    @pl.when(si == n_s_steps - 1)
    def _store():
        for h in range(n_kv):
            l = jnp.maximum(l_ref[h][:, :1], 1e-30)
            o_ref[0, h] = (acc_ref[h] / l).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=(
    "kv_block", "softcap", "block_s", "interpret"))
def int8_kv_attention_pallas(q: jax.Array, k_codes: jax.Array,
                             k_scales: jax.Array, v_codes: jax.Array,
                             v_scales: jax.Array, kpos: jax.Array, *,
                             kv_block: int, softcap: float = 0.0,
                             block_s: int = DEFAULT_BLOCK_S,
                             interpret: bool = True) -> jax.Array:
    """q: (B, KV, R, hd) pre-scaled; k/v codes: (B, S, KV, hd) int8;
    k/v scales: (B, S, KV, hd//kv_block) f32; kpos: (B, S) int32 with -1
    marking invalid slots. Returns (B, KV, R, hd) in q.dtype.

    Shape divisibility (S % block_s == 0) is the caller's contract
    (ops.py pads with kpos=-1 sentinels); on the chip block_s must also
    be a multiple of 128 or all of S (kpos is tiled along lanes).
    """
    b, kv, r, hd = q.shape
    s_len = k_codes.shape[1]
    nb = hd // kv_block
    assert k_scales.shape[-1] == nb, (k_scales.shape, kv_block)
    assert s_len % block_s == 0, (s_len, block_s)
    grid = (b, s_len // block_s)
    kernel = functools.partial(_kv_attn_kernel, kv_block=kv_block,
                               softcap=softcap, n_s_steps=grid[1],
                               out_dtype=q.dtype)
    codes = pl.BlockSpec((1, block_s, kv, hd), lambda i, s: (i, s, 0, 0))
    scales = pl.BlockSpec((1, block_s, kv, nb), lambda i, s: (i, s, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, kv, r, hd), lambda i, s: (i, 0, 0, 0)),
            codes, scales, codes, scales,
            pl.BlockSpec((1, 1, block_s), lambda i, s: (i, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, kv, r, hd), lambda i, s: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, r, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((kv, r, hd), jnp.float32),
                        pltpu.VMEM((kv, r, _MIN_LANES), jnp.float32),
                        pltpu.VMEM((kv, r, _MIN_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k_codes, k_scales, v_codes, v_scales, kpos[:, None, :])
