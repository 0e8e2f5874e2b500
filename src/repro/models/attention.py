"""Attention blocks: GQA (full / sliding-window / local), MLA, cross-attn.

Three entry points per variant:

  - ``*_forward``  — train/prefill over a full (B, S, D) sequence. Scores are
    never materialized at (S, S): queries are processed in chunks with an
    online-softmax accumulator (flash-attention recurrence in pure JAX via
    ``lax.scan``), keeping peak memory at (B, H, qc, S).
  - ``*_decode``   — one new token against a cache.
  - ``init_*`` / ``init_*_cache`` — params and cache constructors.

Cache layouts (per layer):
  GQA full:   {"k": (B, S_max, KV, hd), "v": ..., } position passed in.
  GQA window: ring buffer (B, W, KV, hd) indexed by pos % W.
  MLA:        {"ckv": (B, S_max, kv_lora_rank), "krope": (B, S_max, r_hd)}
              — the compressed latent is cached, not per-head K/V; this is
              MLA's decode-memory win and it is preserved here.

Quantized GQA caches (``dtype="int8"``, the ``serve.kv_cache=int8`` knob):
the ``"k"``/``"v"`` leaves hold int8 codes at the same shapes, paired with
per-(position, kv-head, block) f32 scale leaves ``"k_scale"``/``"v_scale"``
(block = ``kv_codec.default_kv_block(head_dim)``) and per-lane f32
error-feedback accumulators ``"k_err"``/``"v_err"`` (B, KV, hd) that decode
appends fold in (``e ← x − dec(enc(x + e))``) so quantization bias doesn't
compound over decode steps. Every leaf keeps batch at axis 1 after layer
stacking, so the slot API in models/transformer.py works unchanged —
``cache_slot_evict``'s lane zeroing resets the accumulator with the lane.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import kv_codec
from repro.kernels import ops as kops
from repro.models.linear import dense, init_dense
from repro.models.layers import apply_rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, KV*n_rep, hd)."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, kv, n_rep, hd)).reshape(b, s, kv * n_rep, hd)


def _attend_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_positions: jax.Array, kv_positions: jax.Array,
                    *, causal: bool, window: int, softcap: float,
                    chunk: int = 512) -> jax.Array:
    """Online-softmax attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, H, hd) (kv already head-repeated).
    positions: (B, Sq) / (B, Sk). Masks: causal (qpos >= kpos) and window
    (kpos > qpos - window) when window > 0. Returns (B, Sq, H, hd).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    chunk = min(chunk, sq)
    n_chunks = -(-sq // chunk)
    pad = n_chunks * chunk - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
    qc = q.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pc = q_positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    kT = k.transpose(0, 2, 3, 1)                     # (B, H, hd, Sk)
    vT = v.transpose(0, 2, 1, 3)                     # (B, H, Sk, hd)

    def one_chunk(carry, xs):
        qi, pi = xs                                  # (B, c, H, hd), (B, c)
        # matmuls stay in the compute dtype with f32 accumulation — an
        # .astype(f32) on kT/vT makes XLA hoist full-precision copies of
        # K/V out of the chunk loop
        s = jnp.einsum("bchd,bhdk->bhck",
                       (qi.astype(jnp.float32) * scale).astype(qi.dtype),
                       kT, preferred_element_type=jnp.float32)
        s = _softcap(s, softcap)
        mask = jnp.ones((b, 1, chunk, sk), bool)
        dq = pi[:, None, :, None]                    # (B,1,c,1)
        dk = kv_positions[:, None, None, :]          # (B,1,1,Sk)
        if causal:
            mask = mask & (dq >= dk)
        if window > 0:
            mask = mask & (dk > dq - window)
        mask = mask & (dq >= 0) & (dk >= 0)          # padding sentinels
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        # fully-masked rows (padding) give uniform p; output is garbage but
        # sliced away below.
        o = jnp.einsum("bhck,bhkd->bchd", p.astype(vT.dtype), vT,
                       preferred_element_type=jnp.float32)
        return carry, o.astype(q.dtype)

    _, out = jax.lax.scan(one_chunk, (), (qc, pc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * chunk, h, hd)
    return out[:, :sq]


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key: jax.Array,
                   bias: bool = False) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {"q": init_dense(ks[0], d, h * hd, bias=bias),
            "k": init_dense(ks[1], d, kv * hd, bias=bias),
            "v": init_dense(ks[2], d, kv * hd, bias=bias),
            "o": init_dense(ks[3], h * hd, d, bias=bias,
                            scale=(h * hd) ** -0.5)}


def _project_qkv(cfg: ModelConfig, p: Dict, x: jax.Array, name: str):
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(p["q"], x, f"{name}.q").reshape(b, s, h, hd)
    k = dense(p["k"], x, f"{name}.k").reshape(b, s, kv, hd)
    v = dense(p["v"], x, f"{name}.v").reshape(b, s, kv, hd)
    return q, k, v


def attention_forward(cfg: ModelConfig, p: Dict, x: jax.Array,
                      positions: jax.Array, *, causal: bool = True,
                      window: int = 0, name: str = "attn",
                      use_rope: Optional[bool] = None) -> jax.Array:
    """Train/prefill self-attention. x: (B, S, D); positions: (B, S)."""
    q, k, v = _project_qkv(cfg, p, x, name)
    if use_rope if use_rope is not None else cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    n_rep = cfg.num_heads // cfg.num_kv_heads
    o = _attend_chunked(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                        positions, positions, causal=causal, window=window,
                        softcap=cfg.attn_logits_softcap)
    b, s, _, _ = o.shape
    return dense(p["o"], o.reshape(b, s, -1), f"{name}.o")


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Dict:
    """``dtype`` is a jnp dtype, or the string sentinel ``"int8"`` for the
    quantized cache layout (codes + scales + error-feedback accumulators,
    module docstring)."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    if isinstance(dtype, str) and dtype == "int8":
        nb = hd // kv_codec.default_kv_block(hd)
        return {"k": jnp.zeros((batch, max_len, kv, hd), jnp.int8),
                "k_scale": jnp.zeros((batch, max_len, kv, nb), jnp.float32),
                "k_err": jnp.zeros((batch, kv, hd), jnp.float32),
                "v": jnp.zeros((batch, max_len, kv, hd), jnp.int8),
                "v_scale": jnp.zeros((batch, max_len, kv, nb), jnp.float32),
                "v_err": jnp.zeros((batch, kv, hd), jnp.float32)}
    return {"k": jnp.zeros((batch, max_len, kv, hd), dtype),
            "v": jnp.zeros((batch, max_len, kv, hd), dtype)}


def kv_cache_quantized(cache: Dict) -> bool:
    """True for the int8 codes+scales cache layout."""
    return "k_scale" in cache


def kv_cache_block(cache: Dict) -> int:
    """Codec block size of a quantized cache, recovered from leaf shapes."""
    return cache["k"].shape[-1] // cache["k_scale"].shape[-1]


def attention_prefill(cfg: ModelConfig, p: Dict, x: jax.Array,
                      positions: jax.Array, cache: Dict, *,
                      window: int = 0, name: str = "attn",
                      start: Optional[int] = None
                      ) -> Tuple[jax.Array, Dict]:
    """Prefill: run causal attention AND populate the cache.

    Full-attn cache: written at [0:S]. Window cache (ring, size W): the last
    W tokens land at slot ``pos % W``.

    ``start`` switches to *continuation* mode (chunked prefill,
    docs/SERVING.md): ``x`` is the chunk of absolute positions
    ``[start, start+S)``, the cache already holds positions ``< start``, and
    queries attend to cached history + the chunk (read-before-write, so a
    ring cache still covers every in-chunk query's window). ``start=None``
    keeps the legacy whole-sequence path bit-for-bit untouched.
    """
    q, k, v = _project_qkv(cfg, p, x, name)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    n_rep = cfg.num_heads // cfg.num_kv_heads
    b, s = x.shape[:2]
    if start is None:
        o = _attend_chunked(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                            positions, positions, causal=True, window=window,
                            softcap=cfg.attn_logits_softcap)
    else:
        # continuation: history keys come from the cache as written by the
        # PREVIOUS chunks (read before this chunk's write — a ring cache
        # then still holds (start-1-W, start-1], which together with the
        # in-chunk keys covers every query's window)
        w_cache = cache["k"].shape[1]
        old_kpos = _cache_key_positions(start - 1, w_cache, window)
        old_kpos = jnp.broadcast_to(old_kpos[None], (b, w_cache))
        if kv_cache_quantized(cache):
            blk = kv_cache_block(cache)
            k_hist = kv_codec.dec_int8_blocks(
                cache["k"], cache["k_scale"], blk).astype(k.dtype)
            v_hist = kv_codec.dec_int8_blocks(
                cache["v"], cache["v_scale"], blk).astype(v.dtype)
        else:
            k_hist = cache["k"].astype(k.dtype)
            v_hist = cache["v"].astype(v.dtype)
        k_all = jnp.concatenate([repeat_kv(k_hist, n_rep),
                                 repeat_kv(k, n_rep)], axis=1)
        v_all = jnp.concatenate([repeat_kv(v_hist, n_rep),
                                 repeat_kv(v, n_rep)], axis=1)
        kv_pos = jnp.concatenate([old_kpos, positions], axis=1)
        o = _attend_chunked(q, k_all, v_all, positions, kv_pos, causal=True,
                            window=window, softcap=cfg.attn_logits_softcap)
    y = dense(p["o"], o.reshape(b, s, -1), f"{name}.o")

    w_cache = cache["k"].shape[1]
    if kv_cache_quantized(cache):
        # quantize then reuse the same three write-branch index ops for the
        # codes AND scales leaves (same shapes up to the trailing dim). The
        # error-feedback accumulators stay untouched at prefill — EF is a
        # decode-append recurrence; prefill writes are one-shot.
        blk = kv_cache_block(cache)
        if window > 0 and w_cache < s:
            ksel, vsel = k[:, -w_cache:], v[:, -w_cache:]
            idx = positions[:, -w_cache:] % w_cache              # (B, W)
        elif window > 0 and start is not None:
            ksel, vsel = k, v
            idx = positions % w_cache                            # (B, S)
        else:
            ksel, vsel, idx = k, v, None
        kq, ksc = kv_codec.enc_int8_blocks(ksel, blk)
        vq, vsc = kv_codec.enc_int8_blocks(vsel, blk)
        if idx is not None:
            bidx = jnp.arange(b)[:, None]
            cache = dict(cache,
                         k=cache["k"].at[bidx, idx].set(kq),
                         k_scale=cache["k_scale"].at[bidx, idx].set(ksc),
                         v=cache["v"].at[bidx, idx].set(vq),
                         v_scale=cache["v_scale"].at[bidx, idx].set(vsc))
        else:
            off = 0 if start is None else start
            upd = jax.lax.dynamic_update_slice
            cache = dict(cache,
                         k=upd(cache["k"], kq, (0, off, 0, 0)),
                         k_scale=upd(cache["k_scale"], ksc, (0, off, 0, 0)),
                         v=upd(cache["v"], vq, (0, off, 0, 0)),
                         v_scale=upd(cache["v_scale"], vsc, (0, off, 0, 0)))
    elif window > 0 and w_cache < s:
        # ring buffer: keep the last W entries, aligned to pos % W
        idx = positions[:, -w_cache:] % w_cache                  # (B, W)
        ksel = k[:, -w_cache:].astype(cache["k"].dtype)
        vsel = v[:, -w_cache:].astype(cache["v"].dtype)
        bidx = jnp.arange(b)[:, None]
        cache = {"k": cache["k"].at[bidx, idx].set(ksel),
                 "v": cache["v"].at[bidx, idx].set(vsel)}
    elif window > 0 and start is not None:
        # ring continuation: the chunk may straddle the wrap point, so the
        # slot-indexed scatter replaces the offset dynamic_update_slice
        idx = positions % w_cache                                # (B, S)
        bidx = jnp.arange(b)[:, None]
        cache = {"k": cache["k"].at[bidx, idx].set(
                     k.astype(cache["k"].dtype)),
                 "v": cache["v"].at[bidx, idx].set(
                     v.astype(cache["v"].dtype))}
    else:
        off = 0 if start is None else start
        cache = {"k": jax.lax.dynamic_update_slice(
                     cache["k"], k.astype(cache["k"].dtype), (0, off, 0, 0)),
                 "v": jax.lax.dynamic_update_slice(
                     cache["v"], v.astype(cache["v"].dtype), (0, off, 0, 0))}
    return y, cache


def _cache_key_positions(last: int, cache_len: int, window: int) -> jax.Array:
    """Absolute position held by each cache slot after ``last`` was written.

    Full cache (window=0): slot i holds position i, valid while i <= last.
    Ring cache: slot i holds the largest p <= last with p % W == i, valid
    only within the window (unwritten slots alias future positions and are
    masked exactly like the warm-up handling in :func:`attention_decode`).
    Returns (cache_len,) int32 with -1 marking invalid slots; ``last=-1``
    (empty cache) marks everything invalid.
    """
    if last < 0:
        return jnp.full((cache_len,), -1, jnp.int32)
    idx = jnp.arange(cache_len, dtype=jnp.int32)
    if window > 0:
        off = (last - idx) % cache_len
        kpos = last - off
        lo = last - min(window, cache_len)
        # kpos < 0: a slot the ring has not reached yet (last < cache_len-1)
        return jnp.where((kpos > lo) & (kpos >= 0), kpos, -1)
    return jnp.where(idx <= last, idx, -1)


def attention_decode(cfg: ModelConfig, p: Dict, x: jax.Array,
                     pos: jax.Array, cache: Dict, *, window: int = 0,
                     name: str = "attn") -> Tuple[jax.Array, Dict]:
    """One-token decode. x: (B, 1, D); pos: (B,) current position."""
    b = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(cfg, p, x, name)
    if cfg.use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)

    cache_len = cache["k"].shape[1]
    slot = (pos % cache_len) if window > 0 else pos
    bidx = jnp.arange(b)
    quantized = kv_cache_quantized(cache)
    if quantized:
        # error-bounded append: fold the lane's accumulated quantization
        # error into the new K/V row before encoding, then keep the fresh
        # residual — e ← x − dec(enc(x + e)) (Karimireddy et al., the wire
        # codec's recurrence applied per lane; cache eviction zeroes the
        # lane and the accumulator with it)
        blk = kv_cache_block(cache)
        kf = k[:, 0].astype(jnp.float32) + cache["k_err"]
        vf = v[:, 0].astype(jnp.float32) + cache["v_err"]
        kq, ksc = kv_codec.enc_int8_blocks(kf, blk)
        vq, vsc = kv_codec.enc_int8_blocks(vf, blk)
        ck = cache["k"].at[bidx, slot].set(kq)
        cks = cache["k_scale"].at[bidx, slot].set(ksc)
        cv = cache["v"].at[bidx, slot].set(vq)
        cvs = cache["v_scale"].at[bidx, slot].set(vsc)
        new_cache = {"k": ck, "k_scale": cks,
                     "k_err": kf - kv_codec.dec_int8_blocks(kq, ksc, blk),
                     "v": cv, "v_scale": cvs,
                     "v_err": vf - kv_codec.dec_int8_blocks(vq, vsc, blk)}
    else:
        ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}

    # key positions for masking
    if window > 0:
        # ring slot i holds absolute position: the largest p <= pos with
        # p % W == i  (invalid until written; mask p > pos handles warmup
        # because unwritten slots alias future positions)
        off = (pos[:, None] - jnp.arange(cache_len)[None, :]) % cache_len
        kpos = pos[:, None] - off                                # (B, W)
        kpos = jnp.where(kpos > pos[:, None] - jnp.minimum(
            jnp.asarray(window), cache_len), kpos, -1)
    else:
        kpos = jnp.arange(cache_len)[None, :].repeat(b, 0)
        kpos = jnp.where(kpos <= pos[:, None], kpos, -1)

    if quantized:
        # fused dequant-attention: int8 history never materializes as a
        # full fp16/f32 tensor in HBM on the pallas path; the dispatcher
        # (impl = serve.kv_impl via ops.kv_attn_default_impl) falls back to
        # the full-dequant XLA oracle off-TPU / over VMEM budget.
        n_rep = h // kv
        qg = (q[:, 0] * hd ** -0.5).reshape(b, kv, n_rep, hd)
        o = kops.int8_kv_attention(qg, ck, cks, cv, cvs, kpos,
                                   kv_block=blk,
                                   softcap=cfg.attn_logits_softcap)
        o = o.astype(x.dtype)
    else:
        # grouped-query attention against the cache WITHOUT materializing an
        # f32 copy of the cache or the head-repeated expansion: the einsum
        # contracts bf16 cache entries directly with f32 accumulation (a
        # repeat_kv(...).astype(f32) form makes XLA hoist a full f32 copy
        # of the entire stacked cache out of the layer scan).
        n_rep = h // kv
        qg = (q[:, 0] * hd ** -0.5).reshape(b, kv, n_rep, hd)
        s = jnp.einsum("bgrd,bsgd->bgrs", qg.astype(ck.dtype), ck,
                       preferred_element_type=jnp.float32)
        s = _softcap(s, cfg.attn_logits_softcap)
        s = jnp.where(kpos[:, None, None, :] >= 0, s, NEG_INF)
        pw = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrs,bsgd->bgrd", pw.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    y = dense(p["o"], o.reshape(b, 1, h * hd), f"{name}.o")
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(cfg: ModelConfig, key: jax.Array,
                         bias: bool = True) -> Dict:
    return init_attention(cfg, key, bias=bias)


def cross_attention_kv(cfg: ModelConfig, p: Dict, enc: jax.Array,
                       name: str = "xattn") -> Dict:
    """Compute the encoder-side K/V once (prefill). enc: (B, Se, D)."""
    b, se, _ = enc.shape
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    k = dense(p["k"], enc, f"{name}.k").reshape(b, se, kv, hd)
    v = dense(p["v"], enc, f"{name}.v").reshape(b, se, kv, hd)
    return {"k": k, "v": v}


def cross_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                    kv_cache: Dict, name: str = "xattn") -> jax.Array:
    """Decoder query against fixed encoder K/V. No positions, no mask."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(p["q"], x, f"{name}.q").reshape(b, s, h, hd)
    n_rep = h // kv
    k = repeat_kv(kv_cache["k"], n_rep)
    v = repeat_kv(kv_cache["v"], n_rep)
    se = k.shape[1]
    qpos = jnp.zeros((b, s), jnp.int32)
    kpos = jnp.zeros((b, se), jnp.int32)
    o = _attend_chunked(q, k, v, qpos, kpos, causal=False, window=0,
                        softcap=0.0)
    return dense(p["o"], o.reshape(b, s, -1), f"{name}.o")


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek-v3)
# ---------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key: jax.Array) -> Dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    return {
        "q_down": init_dense(ks[0], d, m.q_lora_rank),
        "q_up": init_dense(ks[1], m.q_lora_rank, h * qk_hd),
        "kv_down": init_dense(ks[2], d, m.kv_lora_rank),
        "k_rope": init_dense(ks[3], d, m.qk_rope_head_dim),
        "k_up": init_dense(ks[4], m.kv_lora_rank, h * m.qk_nope_head_dim),
        "v_up": init_dense(ks[5], m.kv_lora_rank, h * m.v_head_dim),
        "o": init_dense(ks[6], h * m.v_head_dim, d,
                        scale=(h * m.v_head_dim) ** -0.5),
    }


def _mla_qkv(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array,
             name: str):
    """Project to (q_nope, q_rope, ckv, k_rope). x: (B, S, D)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    ql = dense(p["q_down"], x, f"{name}.q_down")
    q = dense(p["q_up"], ql, f"{name}.q_up").reshape(
        b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = dense(p["kv_down"], x, f"{name}.kv_down")           # (B,S,rank)
    k_rope = dense(p["k_rope"], x, f"{name}.k_rope")          # (B,S,r_hd)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, ckv, k_rope


def _mla_attend(cfg: ModelConfig, p: Dict, q_nope, q_rope, ckv, k_rope,
                q_positions, kv_positions, name: str, causal: bool = True):
    """Expand latent → per-head K/V and run chunked attention."""
    m = cfg.mla
    b, sk = ckv.shape[:2]
    h = cfg.num_heads
    k_nope = dense(p["k_up"], ckv, f"{name}.k_up").reshape(
        b, sk, h, m.qk_nope_head_dim)
    v = dense(p["v_up"], ckv, f"{name}.v_up").reshape(b, sk, h, m.v_head_dim)
    # decoupled-rope score: concat nope+rope dims on both sides; k_rope is
    # shared across heads.
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    kr = jnp.broadcast_to(k_rope[:, :, None, :],
                          (b, sk, h, m.qk_rope_head_dim))
    k = jnp.concatenate([k_nope, kr], axis=-1)
    # pad v to qk head dim for the shared attend, slice after
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.v_head_dim < qk_hd:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_hd - m.v_head_dim)))
    o = _attend_chunked(q, k, v, q_positions, kv_positions, causal=causal,
                        window=0, softcap=cfg.attn_logits_softcap)
    o = o[..., :m.v_head_dim]
    sq = o.shape[1]
    return dense(p["o"], o.reshape(b, sq, -1), f"{name}.o")


def mla_forward(cfg: ModelConfig, p: Dict, x: jax.Array,
                positions: jax.Array, name: str = "attn") -> jax.Array:
    q_nope, q_rope, ckv, k_rope = _mla_qkv(cfg, p, x, positions, name)
    return _mla_attend(cfg, p, q_nope, q_rope, ckv, k_rope,
                       positions, positions, name)


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict:
    m = cfg.mla
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}


def mla_prefill(cfg: ModelConfig, p: Dict, x: jax.Array,
                positions: jax.Array, cache: Dict,
                name: str = "attn", start: Optional[int] = None
                ) -> Tuple[jax.Array, Dict]:
    """``start`` = chunked-prefill continuation, as in attention_prefill:
    queries attend cached latents (positions < start) + the chunk."""
    q_nope, q_rope, ckv, k_rope = _mla_qkv(cfg, p, x, positions, name)
    if start is None:
        y = _mla_attend(cfg, p, q_nope, q_rope, ckv, k_rope, positions,
                        positions, name)
    else:
        b = x.shape[0]
        s_max = cache["ckv"].shape[1]
        old_kpos = _cache_key_positions(start - 1, s_max, 0)
        old_kpos = jnp.broadcast_to(old_kpos[None], (b, s_max))
        ckv_all = jnp.concatenate([cache["ckv"].astype(x.dtype), ckv],
                                  axis=1)
        krope_all = jnp.concatenate([cache["krope"].astype(x.dtype), k_rope],
                                    axis=1)
        kv_pos = jnp.concatenate([old_kpos, positions], axis=1)
        y = _mla_attend(cfg, p, q_nope, q_rope, ckv_all, krope_all,
                        positions, kv_pos, name)
    off = 0 if start is None else start
    cache = {"ckv": jax.lax.dynamic_update_slice(
                 cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, off, 0)),
             "krope": jax.lax.dynamic_update_slice(
                 cache["krope"], k_rope.astype(cache["krope"].dtype),
                 (0, off, 0))}
    return y, cache


def mla_decode(cfg: ModelConfig, p: Dict, x: jax.Array, pos: jax.Array,
               cache: Dict, name: str = "attn") -> Tuple[jax.Array, Dict]:
    """One-token MLA decode against the *latent* cache."""
    b = x.shape[0]
    q_nope, q_rope, ckv, k_rope = _mla_qkv(cfg, p, x, pos[:, None], name)
    bidx = jnp.arange(b)
    cache = {"ckv": cache["ckv"].at[bidx, pos].set(
                 ckv[:, 0].astype(cache["ckv"].dtype)),
             "krope": cache["krope"].at[bidx, pos].set(
                 k_rope[:, 0].astype(cache["krope"].dtype))}
    s_max = cache["ckv"].shape[1]
    kpos = jnp.arange(s_max)[None, :].repeat(b, 0)
    kpos = jnp.where(kpos <= pos[:, None], kpos, -1)
    qpos = pos[:, None]
    y = _mla_attend(cfg, p, q_nope, q_rope,
                    cache["ckv"].astype(x.dtype),
                    cache["krope"].astype(x.dtype),
                    qpos, kpos, name, causal=True)
    return y, cache
