"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel allclose tests and the XLA
fallback implementations used on non-TPU backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def hessian_accum_ref(x: jax.Array) -> jax.Array:
    """H = X^T X with fp32 accumulation. x: (n, d) any float dtype."""
    xf = x.astype(jnp.float32)
    return xf.T @ xf


def w4a16_matmul_ref(x: jax.Array, packed: jax.Array, scales: jax.Array,
                     zeros: jax.Array, group_size: int) -> jax.Array:
    """y = x @ dequant(W)^T.

    x:      (m, k) float (bf16/f32)
    packed: (n, k//2) uint8 — two 4-bit codes per byte, low nibble = even col
    scales: (n, k//group_size) f32
    zeros:  (n, k//group_size) f32 (integer-valued)
    returns (m, n) in x.dtype, fp32 accumulation.
    """
    n, kh = packed.shape
    k = kh * 2
    lo = (packed & jnp.uint8(0x0F)).astype(jnp.float32)
    hi = ((packed >> 4) & jnp.uint8(0x0F)).astype(jnp.float32)
    codes = jnp.stack([lo, hi], axis=-1).reshape(n, k)
    s = jnp.repeat(scales.astype(jnp.float32), group_size, axis=1)
    z = jnp.repeat(zeros.astype(jnp.float32), group_size, axis=1)
    w = (codes - z) * s                                   # (n, k) f32
    y = jnp.dot(x.astype(jnp.float32), w.T,
                preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def int8_kv_attention_ref(q: jax.Array, k_codes: jax.Array,
                          k_scales: jax.Array, v_codes: jax.Array,
                          v_scales: jax.Array, kpos: jax.Array,
                          kv_block: int, softcap: float = 0.0) -> jax.Array:
    """Decode GQA attention against an int8 KV cache, full-dequant oracle.

    q: (B, KV, R, hd) pre-scaled (hd^-0.5 folded in by the caller);
    k/v codes: (B, S, KV, hd) int8; k/v scales: (B, S, KV, hd//kv_block)
    f32; kpos: (B, S) int32, -1 marks invalid slots (the caller encodes
    causal/window validity into kpos). Returns (B, KV, R, hd) in q.dtype
    with f32 score/value accumulation. Materializes the dequantized cache
    — the HBM cost the fused kernel avoids.
    """
    from repro.kernels import kv_codec
    k = kv_codec.dec_int8_blocks(k_codes, k_scales, kv_block)  # (B,S,KV,hd)
    v = kv_codec.dec_int8_blocks(v_codes, v_scales, kv_block)
    s = jnp.einsum("bgrd,bsgd->bgrs", q.astype(jnp.float32), k,
                   preferred_element_type=jnp.float32)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(kpos[:, None, None, :] >= 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", p, v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def selective_scan_ref(u: jax.Array, dt: jax.Array, bm: jax.Array,
                       cm: jax.Array, a_log: jax.Array, d_skip: jax.Array,
                       h0: jax.Array):
    """Mamba-1 diagonal SSM, sequential scan oracle.

    u/dt: (B, S, d); bm/cm: (B, S, n); a_log: (d, n) (A = -exp(a_log));
    d_skip: (d,); h0: (B, d, n). Returns (y (B,S,d) in u.dtype, h_last).
    """
    A = -jnp.exp(a_log.astype(jnp.float32))

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs                    # (B,d),(B,d),(B,n),(B,n)
        a_t = jnp.exp(dt_t[..., None] * A[None])    # (B, d, n)
        h = a_t * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        y_t = jnp.einsum("bdn,bn->bd", h, c_t) \
            + u_t * d_skip.astype(jnp.float32)[None]
        return h, y_t

    xs = (u.astype(jnp.float32).transpose(1, 0, 2),
          dt.astype(jnp.float32).transpose(1, 0, 2),
          bm.astype(jnp.float32).transpose(1, 0, 2),
          cm.astype(jnp.float32).transpose(1, 0, 2))
    h_last, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return ys.transpose(1, 0, 2).astype(u.dtype), h_last.astype(h0.dtype)


def gptq_block_ref(w, hinv_u, *, bits: int = 4, group_size: int = 128,
                   blocksize: int = 128, symmetric: bool = False):
    """Pure-NumPy GPTQ lazy-block sweep — the oracle for gptq_block.

    w: (out, in) or (B, out, in); hinv_u: matching (in, in) / (B, in, in)
    upper Cholesky of the damped inverse Hessian.  Returns (w_q, scales,
    zeros, err) with err the scalar Σerr² per member.  Mirrors
    ``core/gptq._gptq_core`` step for step (AutoGPTQ semantics: group
    qparams refresh from the error-compensated weights at group entry).
    """
    if np.ndim(w) == 3:
        outs = [gptq_block_ref(np.asarray(w)[i], np.asarray(hinv_u)[i],
                               bits=bits, group_size=group_size,
                               blocksize=blocksize, symmetric=symmetric)
                for i in range(np.shape(w)[0])]
        return tuple(np.stack([o[k] for o in outs]) for k in range(4))

    w = np.array(w, np.float32)
    u = np.array(hinv_u, np.float32)
    out_dim, in_dim = w.shape
    assert in_dim % blocksize == 0 and blocksize % group_size == 0
    qmax = 2.0 ** bits - 1.0
    n_groups = in_dim // group_size
    scales = np.zeros((out_dim, n_groups), np.float32)
    zeros = np.zeros((out_dim, n_groups), np.float32)
    tot_err = np.float32(0.0)

    for c1 in range(0, in_dim, blocksize):
        c2 = c1 + blocksize
        wb = w[:, c1:c2].copy()
        ub = u[c1:c2, c1:c2]
        errb = np.zeros_like(wb)
        scale = zero = None
        for j in range(blocksize):
            if j % group_size == 0:
                g = (c1 + j) // group_size
                wg = wb[:, (j // group_size) * group_size:
                        (j // group_size + 1) * group_size]
                if symmetric:
                    absmax = np.max(np.abs(wg), axis=1)
                    scale = np.maximum(
                        absmax / (2.0 ** (bits - 1) - 1), 1e-8)
                    zero = np.zeros_like(scale)
                else:
                    wmax = np.maximum(np.max(wg, axis=1), 0.0)
                    wmin = np.minimum(np.min(wg, axis=1), 0.0)
                    scale = np.maximum((wmax - wmin) / qmax, 1e-8)
                    zero = np.clip(np.round(-wmin / scale), 0.0, qmax)
                scales[:, g] = scale
                zeros[:, g] = zero
            wcol = wb[:, j]
            d = ub[j, j]
            if symmetric:
                lo, hi = -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1
                q = np.clip(np.round(wcol / scale), lo, hi) * scale
            else:
                q = (np.clip(np.round(wcol / scale) + zero, 0.0, qmax)
                     - zero) * scale
            err = (wcol - q) / d
            wb[:, j + 1:] -= err[:, None] * ub[j, j + 1:][None, :]
            wb[:, j] = q
            errb[:, j] = err
        w[:, c2:] -= errb @ u[c1:c2, c2:]
        w[:, c1:c2] = wb
        tot_err += np.sum(errb * errb)
    return w, scales, zeros, np.float32(tot_err)


def rpiq_block_ref(w_init, w_fp, x_last, hinv_blocks, scales, zeros, *,
                   bits: int = 4, group_size: int = 128,
                   block_size: int = 128, alpha: float = 0.01,
                   t_max: int = 5, early_stop: bool = True,
                   symmetric: bool = False):
    """Pure-NumPy RPIQ stage-2 closed loop — the oracle for rpiq_block.

    w_init/w_fp: (out, in) or (B, out, in); x_last matches with (n, in)
    trailing dims; hinv_blocks: (M, bs, bs) / (B, M, bs, bs) explicit
    blockwise curvature inverses (``core/rpiq._block_curvature_inv``).
    Returns the RPIQResult tuple ``(w_q, w_cont, loss_history, proj_loss,
    iters_run)``.  Mirrors ``core/rpiq._rpiq_core`` step for step:
    directed residual, one-matmul LS solve against the pre-factored
    inverse, grid projection, damped update, immediate Y_q update, Γ
    early stop and strict-improvement best-projection tracking.
    """
    if np.ndim(w_init) == 3:
        outs = [rpiq_block_ref(np.asarray(w_init)[i], np.asarray(w_fp)[i],
                               np.asarray(x_last)[i],
                               np.asarray(hinv_blocks)[i],
                               np.asarray(scales)[i], np.asarray(zeros)[i],
                               bits=bits, group_size=group_size,
                               block_size=block_size, alpha=alpha,
                               t_max=t_max, early_stop=early_stop,
                               symmetric=symmetric)
                for i in range(np.shape(w_init)[0])]
        return tuple(np.stack([o[k] for o in outs]) for k in range(5))

    w0 = np.array(w_init, np.float32)
    x = np.array(x_last, np.float32)
    hinv = np.array(hinv_blocks, np.float32)
    out_dim, in_dim = w0.shape
    assert in_dim % block_size == 0 and block_size % group_size == 0
    n_blocks = in_dim // block_size
    y_orig = x @ np.array(w_fp, np.float32).T
    s = np.repeat(np.array(scales, np.float32), group_size, axis=1)
    z = np.repeat(np.array(zeros, np.float32), group_size, axis=1)

    def project(b, sl, zl):
        if symmetric:
            lo, hi = -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1
            return np.clip(np.round(b / sl), lo, hi) * sl
        qmax = 2.0 ** bits - 1.0
        q = np.clip(np.round(b / sl) + zl, 0.0, qmax)
        return (q - zl) * sl

    w = w0.copy()
    y_q = x @ w.T
    hist = np.full(t_max + 1, np.inf, np.float32)
    hist[0] = np.float32(np.sum((y_orig - y_q) ** 2))
    best_w, best_loss = w0.copy(), hist[0]
    iters = 0
    for t in range(t_max):
        for i in range(n_blocks):
            c1, c2 = i * block_size, (i + 1) * block_size
            b_old = w[:, c1:c2]
            x_i = x[:, c1:c2]
            y_qi = x_i @ b_old.T
            d_i = y_orig - (y_q - y_qi)
            rhs = x_i.T @ d_i
            b_star = (hinv[i] @ rhs).T
            b_proj = project(b_star, s[:, c1:c2], z[:, c1:c2])
            b_new = b_old + np.float32(alpha) * (b_proj - b_old)
            y_q = y_q - y_qi + x_i @ b_new.T
            w = w.copy()
            w[:, c1:c2] = b_new
        gamma = np.float32(np.sum((y_orig - y_q) ** 2))
        hist[t + 1] = gamma
        w_proj = project(w, s, z)
        ploss = np.float32(np.sum((y_orig - x @ w_proj.T) ** 2))
        iters = t + 1
        if ploss < best_loss:
            best_w, best_loss = w_proj, ploss
        if early_stop and gamma >= hist[t] * (1.0 - 1e-6):
            break
    return (best_w, w, hist, np.float32(best_loss), np.int32(iters))
