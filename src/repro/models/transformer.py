"""Model assembly: decoder-only LMs (all families) and enc-dec (whisper).

Layer stacking
--------------
``layer_specs(cfg)`` expands the config's block pattern into one
``(mixer, mlp)`` spec per layer; ``segment_specs`` groups the stack into
*segments* — either ``k`` repeats of a short periodic super-block (scanned
with ``lax.scan`` over stacked params → small HLO even for 61-layer models)
or a run of identical layers. recurrentgemma's (rglru, rglru, local)×12+2
and deepseek's 3-dense + 58-MoE both segment cleanly.

Param layout (canonical, used by training, serving and the quantization
pipeline):

  params = {
    "embed": {...}, "final_norm": {...}, "lm_head"?: {...},
    "blocks": [seg0, seg1, ...]   # seg = {"sub0": {...}, "sub1": ...}
                                  # every leaf stacked with leading (count,)
    "mtp"?: {...}
  }

Eager per-layer access (calibration pipeline, CPU) uses
``tree_map(lambda a: a[i], seg)``.

Sharding hints: the residual stream gets `shard_hint(h, "dp", None/"sp",
None)` at segment boundaries; actual specs are injected by
``repro.distributed.sharding.use_rules`` — models stay mesh-agnostic.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models import recurrent as rec
from repro.models import moe as moe_mod
from repro.models.layers import (embed, init_embed, init_mlp, init_norm,
                                 mlp, norm, sinusoidal_positions, unembed)
from repro.models.linear import init_dense
from repro.distributed.sharding import shard_hint


LayerSpec = Tuple[str, str]     # (mixer, mlp) — static strings


class Segment(NamedTuple):
    specs: Tuple[LayerSpec, ...]   # super-block period
    count: int                     # repeats


# ---------------------------------------------------------------------------
# Spec expansion / segmentation
# ---------------------------------------------------------------------------

def layer_specs(cfg: ModelConfig) -> Tuple[LayerSpec, ...]:
    out: List[LayerSpec] = []
    for i, kind in enumerate(cfg.layer_kinds):
        if kind in ("mamba",):
            mixer, mlp_kind = "mamba", "none"
        elif kind in ("rglru",):
            mixer, mlp_kind = "rglru", "dense"
        else:                       # attn | swa | local
            mixer = "mla" if cfg.mla.enabled else kind
            mlp_kind = "dense"
        if cfg.moe.num_experts > 0 and mlp_kind == "dense":
            if i >= cfg.moe.first_dense_layers:
                mlp_kind = "moe"
        out.append((mixer, mlp_kind))
    return tuple(out)


def segment_specs(specs: Sequence[LayerSpec],
                  pattern_len: int) -> List[Segment]:
    """Greedy tiling: periodic super-blocks where they repeat, runs else."""
    segs: List[Segment] = []
    i, n = 0, len(specs)
    while i < n:
        j = i
        while j < n and specs[j] == specs[i]:
            j += 1
        run1 = j - i
        runq = 0
        q = pattern_len
        if q > 1 and i + q <= n:
            base = tuple(specs[i:i + q])
            while (i + (runq + 1) * q <= n
                   and tuple(specs[i + runq * q:i + (runq + 1) * q]) == base):
                runq += 1
        if q > 1 and runq * q > run1:
            segs.append(Segment(tuple(specs[i:i + q]), runq))
            i += runq * q
        else:
            segs.append(Segment((specs[i],), run1))
            i += run1
    return segs


def segments(cfg: ModelConfig) -> List[Segment]:
    return segment_specs(layer_specs(cfg), len(cfg.block_pattern))


# ---------------------------------------------------------------------------
# Single layer: init / forward / prefill / decode
# ---------------------------------------------------------------------------

def _window_of(cfg: ModelConfig, mixer: str) -> int:
    return cfg.window_size if mixer in ("swa", "local") else 0


def init_layer(cfg: ModelConfig, spec: LayerSpec, key: jax.Array) -> Dict:
    mixer, mlp_kind = spec
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": init_norm(cfg, cfg.d_model)}
    if mixer == "mla":
        p["mixer"] = attn.init_mla(cfg, k1)
    elif mixer in ("attn", "swa", "local"):
        p["mixer"] = attn.init_attention(cfg, k1,
                                         bias=cfg.norm == "layernorm")
    elif mixer == "rglru":
        p["mixer"] = rec.init_rglru_block(cfg, k1)
    elif mixer == "mamba":
        p["mixer"] = rec.init_mamba_block(cfg, k1)
    else:
        raise ValueError(mixer)
    if mlp_kind != "none":
        p["norm2"] = init_norm(cfg, cfg.d_model)
        if mlp_kind == "moe":
            p["mlp"] = moe_mod.init_moe(cfg, k2)
        else:
            p["mlp"] = init_mlp(cfg, k2, cfg.d_model, cfg.d_ff,
                                bias=cfg.norm == "layernorm")
    return p


def layer_forward(cfg: ModelConfig, spec: LayerSpec, p: Dict, h: jax.Array,
                  positions: jax.Array, name: str = ""
                  ) -> Tuple[jax.Array, jax.Array]:
    """Train-mode (no cache). Returns (h, aux_loss)."""
    mixer, mlp_kind = spec
    aux = jnp.zeros((), jnp.float32)
    hn = norm(cfg, p["norm1"], h)
    if mixer == "mla":
        y = attn.mla_forward(cfg, p["mixer"], hn, positions,
                             name=f"{name}mixer")
    elif mixer in ("attn", "swa", "local"):
        y = attn.attention_forward(cfg, p["mixer"], hn, positions,
                                   window=_window_of(cfg, mixer),
                                   name=f"{name}mixer")
    elif mixer == "rglru":
        y, _ = rec.rglru_block(cfg, p["mixer"], hn, None,
                               name=f"{name}mixer")
    elif mixer == "mamba":
        y, _ = rec.mamba_block(cfg, p["mixer"], hn, None,
                               name=f"{name}mixer")
    h = h + y
    if mlp_kind != "none":
        hn = norm(cfg, p["norm2"], h)
        if mlp_kind == "moe":
            out = moe_mod.moe_ffn(cfg, p["mlp"], hn, name=f"{name}mlp")
            h = h + out.y
            aux = aux + out.aux_loss
        else:
            h = h + mlp(cfg, p["mlp"], hn, name=f"{name}mlp")
    return h, aux


def _float_cache_dtype(dtype):
    """Resolve the ``"int8"`` sentinel to bf16 for cache kinds that stay in
    float: MLA latents (already the compressed-memory form), recurrent
    states, and enc-dec cross-KV (computed once, not append-quantized)."""
    return jnp.bfloat16 if isinstance(dtype, str) and dtype == "int8" \
        else dtype


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype=jnp.bfloat16) -> Any:
    """``dtype`` may be the string sentinel ``"int8"``: GQA full/ring
    self-attention caches then store int8 codes + f32 scales + error
    accumulators (attention.init_kv_cache); other cache kinds keep bf16."""
    mixer, _ = spec
    if mixer == "mla":
        return attn.init_mla_cache(cfg, batch, max_len,
                                   _float_cache_dtype(dtype))
    if mixer in ("attn",):
        return attn.init_kv_cache(cfg, batch, max_len, dtype)
    if mixer in ("swa", "local"):
        w = min(cfg.window_size, max_len)
        return attn.init_kv_cache(cfg, batch, w, dtype)
    if mixer == "rglru":
        return rec.init_rglru_state(cfg, batch, _float_cache_dtype(dtype))
    if mixer == "mamba":
        return rec.init_mamba_state(cfg, batch, _float_cache_dtype(dtype))
    raise ValueError(mixer)


def layer_prefill(cfg: ModelConfig, spec: LayerSpec, p: Dict, h: jax.Array,
                  positions: jax.Array, cache: Any, name: str = "",
                  start: Optional[int] = None) -> Tuple[jax.Array, Any]:
    """``start`` marks a chunked-prefill continuation (attention variants
    attend cache + chunk; recurrent states continue naturally)."""
    mixer, mlp_kind = spec
    hn = norm(cfg, p["norm1"], h)
    if mixer == "mla":
        y, cache = attn.mla_prefill(cfg, p["mixer"], hn, positions, cache,
                                    name=f"{name}mixer", start=start)
    elif mixer in ("attn", "swa", "local"):
        y, cache = attn.attention_prefill(cfg, p["mixer"], hn, positions,
                                          cache,
                                          window=_window_of(cfg, mixer),
                                          name=f"{name}mixer", start=start)
    elif mixer == "rglru":
        y, cache = rec.rglru_block(cfg, p["mixer"], hn, cache,
                                   name=f"{name}mixer")
    elif mixer == "mamba":
        y, cache = rec.mamba_block(cfg, p["mixer"], hn, cache,
                                   name=f"{name}mixer")
    h = h + y
    if mlp_kind != "none":
        hn = norm(cfg, p["norm2"], h)
        if mlp_kind == "moe":
            h = h + moe_mod.moe_ffn(cfg, p["mlp"], hn,
                                    name=f"{name}mlp").y
        else:
            h = h + mlp(cfg, p["mlp"], hn, name=f"{name}mlp")
    return h, cache


def layer_decode(cfg: ModelConfig, spec: LayerSpec, p: Dict, h: jax.Array,
                 pos: jax.Array, cache: Any, name: str = ""
                 ) -> Tuple[jax.Array, Any]:
    mixer, mlp_kind = spec
    hn = norm(cfg, p["norm1"], h)
    if mixer == "mla":
        y, cache = attn.mla_decode(cfg, p["mixer"], hn, pos, cache,
                                   name=f"{name}mixer")
    elif mixer in ("attn", "swa", "local"):
        y, cache = attn.attention_decode(cfg, p["mixer"], hn, pos, cache,
                                         window=_window_of(cfg, mixer),
                                         name=f"{name}mixer")
    elif mixer == "rglru":
        y, cache = rec.rglru_decode(cfg, p["mixer"], hn, cache,
                                    name=f"{name}mixer")
    elif mixer == "mamba":
        y, cache = rec.mamba_decode(cfg, p["mixer"], hn, cache,
                                    name=f"{name}mixer")
    h = h + y
    if mlp_kind != "none":
        hn = norm(cfg, p["norm2"], h)
        if mlp_kind == "moe":
            h = h + moe_mod.moe_ffn(cfg, p["mlp"], hn,
                                    name=f"{name}mlp").y
        else:
            h = h + mlp(cfg, p["mlp"], hn, name=f"{name}mlp")
    return h, cache


# ---------------------------------------------------------------------------
# Stacked segments
# ---------------------------------------------------------------------------

def _stack_trees(trees: List[Any]) -> Any:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_blocks(cfg: ModelConfig, key: jax.Array) -> List[Dict]:
    out = []
    li = 0
    for seg in segments(cfg):
        elems = []
        for c in range(seg.count):
            sub = {}
            for s_i, spec in enumerate(seg.specs):
                sub[f"sub{s_i}"] = init_layer(
                    cfg, spec, jax.random.fold_in(key, li))
                li += 1
            elems.append(sub)
        out.append(_stack_trees(elems))
    return out


def init_block_caches(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> List[Any]:
    out = []
    for seg in segments(cfg):
        sub = {f"sub{i}": init_layer_cache(cfg, spec, batch, max_len, dtype)
               for i, spec in enumerate(seg.specs)}
        out.append(jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (seg.count,) + a.shape)
            .copy() if seg.count > 1 else a[None], sub))
    return out


def _seg_take(seg_params: Any, i) -> Any:
    return jax.tree_util.tree_map(lambda a: a[i], seg_params)


def blocks_forward(cfg: ModelConfig, blocks: List[Dict], h: jax.Array,
                   positions: jax.Array, *, remat: bool = False,
                   unroll_eager: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Run all segments (train mode). Returns (h, aux_loss_sum)."""
    aux = jnp.zeros((), jnp.float32)
    segs = segments(cfg)
    for seg, seg_params in zip(segs, blocks):
        def superblock(carry, elem_params, _specs=seg.specs):
            h, aux = carry
            for s_i, spec in enumerate(_specs):
                h, a = layer_forward(cfg, spec, elem_params[f"sub{s_i}"], h,
                                     positions)
                aux = aux + a
            h = shard_hint(h, "act")
            return (h, aux), None

        if unroll_eager:
            for c in range(seg.count):
                (h, aux), _ = superblock((h, aux), _seg_take(seg_params, c))
        else:
            fn = superblock
            if remat:
                fn = jax.checkpoint(superblock,
                                    prevent_cse=False)
            (h, aux), _ = jax.lax.scan(fn, (h, aux), seg_params)
    return h, aux


def blocks_prefill(cfg: ModelConfig, blocks: List[Dict], h: jax.Array,
                   positions: jax.Array, caches: List[Any],
                   unroll_eager: bool = False,
                   start: Optional[int] = None
                   ) -> Tuple[jax.Array, List[Any]]:
    segs = segments(cfg)
    new_caches = []
    for seg, seg_params, seg_cache in zip(segs, blocks, caches):
        def superblock(h, xs, _specs=seg.specs):
            elem_params, elem_cache = xs
            out_cache = {}
            for s_i, spec in enumerate(_specs):
                h, c = layer_prefill(cfg, spec, elem_params[f"sub{s_i}"], h,
                                     positions, elem_cache[f"sub{s_i}"],
                                     start=start)
                out_cache[f"sub{s_i}"] = c
            h = shard_hint(h, "act")
            return h, out_cache

        if unroll_eager:
            ncs = []
            for c in range(seg.count):
                h, nc = superblock(h, (_seg_take(seg_params, c),
                                       _seg_take(seg_cache, c)))
                ncs.append(nc)
            new_caches.append(_stack_trees(ncs))
        else:
            h, nc = jax.lax.scan(superblock, h, (seg_params, seg_cache))
            new_caches.append(nc)
    return h, new_caches


def blocks_decode(cfg: ModelConfig, blocks: List[Dict], h: jax.Array,
                  pos: jax.Array, caches: List[Any],
                  unroll_eager: bool = False
                  ) -> Tuple[jax.Array, List[Any]]:
    segs = segments(cfg)
    new_caches = []
    for seg, seg_params, seg_cache in zip(segs, blocks, caches):
        def superblock(h, xs, _specs=seg.specs):
            elem_params, elem_cache = xs
            out_cache = {}
            for s_i, spec in enumerate(_specs):
                h, c = layer_decode(cfg, spec, elem_params[f"sub{s_i}"], h,
                                    pos, elem_cache[f"sub{s_i}"])
                out_cache[f"sub{s_i}"] = c
            return h, out_cache

        if unroll_eager:
            ncs = []
            for c in range(seg.count):
                h, nc = superblock(h, (_seg_take(seg_params, c),
                                       _seg_take(seg_cache, c)))
                ncs.append(nc)
            new_caches.append(_stack_trees(ncs))
        else:
            h, nc = jax.lax.scan(superblock, h, (seg_params, seg_cache))
            new_caches.append(nc)
    return h, new_caches


# ---------------------------------------------------------------------------
# Decoder-only LM
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array) -> Dict:
    k_e, k_b, k_h, k_m = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": init_embed(k_e, cfg.vocab_size, cfg.d_model),
        "blocks": init_blocks(cfg, k_b),
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(k_h, cfg.d_model, cfg.vocab_size)
    if cfg.mtp_depth > 0:
        params["mtp"] = {
            "proj": init_dense(k_m, 2 * cfg.d_model, cfg.d_model),
            "norm": init_norm(cfg, cfg.d_model),
            "layer": init_layer(cfg, layer_specs(cfg)[-1],
                                jax.random.fold_in(k_m, 1)),
        }
    return params


def _embed_inputs(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                  embeds: Optional[jax.Array]) -> jax.Array:
    """Token embeddings, with optional frontend embeds prepended."""
    dtype = jnp.dtype(cfg.dtype)
    h = embed(params["embed"], tokens, dtype)
    if embeds is not None:
        h = jnp.concatenate([embeds.astype(dtype), h], axis=1)
    return h


def forward(cfg: ModelConfig, params: Dict, tokens: jax.Array,
            embeds: Optional[jax.Array] = None, *, remat: bool = False,
            unroll_eager: bool = False, return_hidden: bool = False):
    """Full-sequence forward. Returns (logits (B,S,V) f32, aux_loss) or,
    with ``return_hidden``, (logits, aux_loss, h_normed) for MTP heads."""
    h = _embed_inputs(cfg, params, tokens, embeds)
    b, s, _ = h.shape
    h = shard_hint(h, "act")
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    h, aux = blocks_forward(cfg, params["blocks"], h, positions,
                            remat=remat, unroll_eager=unroll_eager)
    h = norm(cfg, params["final_norm"], h)
    logits = unembed(cfg, params, h)
    logits = shard_hint(logits, "logits")
    if return_hidden:
        return logits, aux, h
    return logits, aux


def mtp_logits(cfg: ModelConfig, params: Dict, h_final: jax.Array,
               tokens: jax.Array) -> jax.Array:
    """deepseek multi-token prediction head: predict t+2 from (h_t, e_{t+1}).

    h_final: (B, S, D) post-final-norm hidden; tokens: (B, S).
    Returns logits (B, S-1, V) for positions t -> token t+2.
    """
    p = params["mtp"]
    dtype = h_final.dtype
    e_next = embed(params["embed"], tokens[:, 1:], dtype)     # (B, S-1, D)
    h_in = jnp.concatenate([h_final[:, :-1], e_next], axis=-1)
    from repro.models.linear import dense
    h0 = dense(p["proj"], h_in, "mtp.proj")
    b, s, _ = h0.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    h1, _ = layer_forward(cfg, layer_specs(cfg)[-1], p["layer"], h0,
                          positions, name="mtp.")
    h1 = norm(cfg, p["norm"], h1)
    return unembed(cfg, params, h1)


def prefill(cfg: ModelConfig, params: Dict, tokens: jax.Array,
            max_len: int, embeds: Optional[jax.Array] = None,
            cache_dtype=jnp.bfloat16, unroll_eager: bool = False
            ) -> Tuple[jax.Array, List[Any]]:
    """Prefill the cache; returns (last-position logits (B, V), caches)."""
    h = _embed_inputs(cfg, params, tokens, embeds)
    b, s, _ = h.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    caches = init_block_caches(cfg, b, max_len, cache_dtype)
    h, caches = blocks_prefill(cfg, params["blocks"], h, positions, caches,
                               unroll_eager=unroll_eager)
    h = norm(cfg, params["final_norm"], h[:, -1:])
    logits = unembed(cfg, params, h)[:, 0]
    return logits, caches


def decode_step(cfg: ModelConfig, params: Dict, token: jax.Array,
                pos: jax.Array, caches: List[Any],
                unroll_eager: bool = False
                ) -> Tuple[jax.Array, List[Any]]:
    """One decode step. token: (B,) int32; pos: (B,) positions of `token`.

    Returns (logits (B, V) f32, new caches).
    """
    dtype = jnp.dtype(cfg.dtype)
    h = embed(params["embed"], token[:, None], dtype)         # (B, 1, D)
    h, caches = blocks_decode(cfg, params["blocks"], h, pos, caches,
                              unroll_eager=unroll_eager)
    h = norm(cfg, params["final_norm"], h)
    logits = unembed(cfg, params, h)[:, 0]
    return logits, caches


def prefill_begin(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                  max_len: int, embeds: Optional[jax.Array] = None,
                  cache_dtype=jnp.bfloat16) -> Tuple[jax.Array, List[Any]]:
    """Incremental-prefill setup: embedded inputs + empty caches.

    The caller feeds slices of the returned ``h`` through
    :func:`prefill_step` one chunk at a time (the continuous-batching
    scheduler does this to interleave prefill with decode ticks)."""
    h = _embed_inputs(cfg, params, tokens, embeds)
    return h, init_block_caches(cfg, h.shape[0], max_len, cache_dtype)


def prefill_step(cfg: ModelConfig, params: Dict, h_chunk: jax.Array,
                 start: int, caches: List[Any],
                 unroll_eager: bool = False
                 ) -> Tuple[jax.Array, List[Any]]:
    """Run one prefill chunk occupying positions [start, start+C)."""
    b, c, _ = h_chunk.shape
    positions = start + jnp.arange(c, dtype=jnp.int32)[None, :].repeat(b, 0)
    return blocks_prefill(cfg, params["blocks"], h_chunk, positions, caches,
                          unroll_eager=unroll_eager, start=start)


def prefill_finish(cfg: ModelConfig, params: Dict, h_last: jax.Array
                   ) -> jax.Array:
    """Next-token logits (B, V) from the final chunk's block output."""
    h = norm(cfg, params["final_norm"], h_last[:, -1:])
    return unembed(cfg, params, h)[:, 0]


def prefill_chunked(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                    max_len: int, chunk: int,
                    embeds: Optional[jax.Array] = None,
                    cache_dtype=jnp.bfloat16, unroll_eager: bool = False
                    ) -> Tuple[jax.Array, List[Any]]:
    """Chunked prefill: positions ``[c, c+chunk)`` at a time, each chunk
    attending cached history + itself (``blocks_prefill(start=...)``).
    Logits/caches are equivalent to single-shot :func:`prefill` (pinned in
    tests/test_serving.py); recurrent states thread through naturally.
    """
    assert chunk > 0
    h, caches = prefill_begin(cfg, params, tokens, max_len, embeds,
                              cache_dtype)
    s = h.shape[1]
    hc = h[:, :0]
    for c0 in range(0, s, chunk):
        hc, caches = prefill_step(cfg, params, h[:, c0:min(s, c0 + chunk)],
                                  c0, caches, unroll_eager=unroll_eager)
    return prefill_finish(cfg, params, hc), caches


# ---------------------------------------------------------------------------
# Slotted-cache API (continuous-batching serving — docs/SERVING.md)
# ---------------------------------------------------------------------------

def cache_slots_like(caches: Any, lanes: int) -> Any:
    """A zeroed slotted decode cache with ``lanes`` lanes, shaped like a
    (batch-1) prefill cache. Every cache leaf in this codebase is stacked
    ``(layers, batch, ...)``, so the lane axis is axis 1 uniformly (GQA /
    ring / MLA / recurrent / enc-dec self+cross)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((a.shape[0], lanes) + a.shape[2:], a.dtype),
        caches)


def cache_slot_insert(caches: Any, src: Any, lane: jax.Array) -> Any:
    """Write a batch-1 cache ``src`` into lane ``lane`` of a slotted cache.

    ``lane`` may be traced (one compiled entry serves every lane). The whole
    lane is overwritten, which is what makes eviction reuse sound: any slots
    a previous occupant wrote are replaced by the new sequence's prefix (and
    positions beyond it are masked by the per-lane ``pos`` at decode time).
    """
    lane = jnp.asarray(lane, jnp.int32)
    return jax.tree_util.tree_map(
        lambda big, small: big.at[:, lane].set(
            small[:, 0].astype(big.dtype)), caches, src)


def cache_slot_evict(caches: Any, lane: jax.Array) -> Any:
    """Zero lane ``lane`` (hygiene only — admission overwrites the lane)."""
    lane = jnp.asarray(lane, jnp.int32)
    return jax.tree_util.tree_map(
        lambda a: a.at[:, lane].set(
            jnp.zeros(a.shape[:1] + a.shape[2:], a.dtype)), caches)


# ---------------------------------------------------------------------------
# Encoder-decoder (whisper)
# ---------------------------------------------------------------------------

def init_encdec_params(cfg: ModelConfig, key: jax.Array) -> Dict:
    """Whisper-style: encoder stack (bidirectional) + decoder with cross."""
    k_e, k_d, k_x, k_emb, k_h = jax.random.split(key, 5)
    enc_layers = []
    for i in range(cfg.encoder_layers):
        enc_layers.append(init_layer(cfg, ("attn", "dense"),
                                     jax.random.fold_in(k_e, i)))
    dec_layers = []
    for i in range(cfg.num_layers):
        li = {"layer": init_layer(cfg, ("attn", "dense"),
                                  jax.random.fold_in(k_d, i)),
              "xnorm": init_norm(cfg, cfg.d_model),
              "xattn": attn.init_cross_attention(
                  cfg, jax.random.fold_in(k_x, i))}
        dec_layers.append(li)
    return {
        "encoder": {"layers": _stack_trees(enc_layers),
                    "final_norm": init_norm(cfg, cfg.d_model)},
        "decoder": {"layers": _stack_trees(dec_layers),
                    "final_norm": init_norm(cfg, cfg.d_model)},
        "embed": init_embed(k_emb, cfg.vocab_size, cfg.d_model),
        "lm_head": init_dense(k_h, cfg.d_model, cfg.vocab_size),
    }


def encode(cfg: ModelConfig, params: Dict, frames: jax.Array,
           unroll_eager: bool = False) -> jax.Array:
    """frames: (B, Se, D) precomputed conv-frontend embeddings (stub)."""
    b, se, _ = frames.shape
    pos_table = sinusoidal_positions(se, cfg.d_model)
    h = frames.astype(jnp.dtype(cfg.dtype)) + pos_table[None].astype(
        jnp.dtype(cfg.dtype))
    positions = jnp.arange(se, dtype=jnp.int32)[None, :].repeat(b, 0)

    def one(h, p):
        hn = norm(cfg, p["norm1"], h)
        y = attn.attention_forward(cfg, p["mixer"], hn, positions,
                                   causal=False, use_rope=False,
                                   name="mixer")
        h = h + y
        hn = norm(cfg, p["norm2"], h)
        h = h + mlp(cfg, p["mlp"], hn, name="mlp")
        return shard_hint(h, "act"), None

    if unroll_eager:
        n = jax.tree_util.tree_leaves(params["encoder"]["layers"])[0].shape[0]
        for i in range(n):
            h, _ = one(h, _seg_take(params["encoder"]["layers"], i))
    else:
        h, _ = jax.lax.scan(one, h, params["encoder"]["layers"])
    return norm(cfg, params["encoder"]["final_norm"], h)


def encdec_forward(cfg: ModelConfig, params: Dict, frames: jax.Array,
                   tokens: jax.Array, unroll_eager: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Teacher-forced training forward. Returns (logits, aux=0)."""
    enc = encode(cfg, params, frames, unroll_eager)
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    h = embed(params["embed"], tokens, dtype)
    h = h + sinusoidal_positions(s, cfg.d_model)[None].astype(dtype)
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)

    def one(h, p):
        lp = p["layer"]
        hn = norm(cfg, lp["norm1"], h)
        y = attn.attention_forward(cfg, lp["mixer"], hn, positions,
                                   causal=True, use_rope=False,
                                   name="layer.mixer")
        h = h + y
        hn = norm(cfg, p["xnorm"], h)
        kv = attn.cross_attention_kv(cfg, p["xattn"], enc, "xattn")
        h = h + attn.cross_attention(cfg, p["xattn"], hn, kv, "xattn")
        hn = norm(cfg, lp["norm2"], h)
        h = h + mlp(cfg, lp["mlp"], hn, name="layer.mlp")
        return shard_hint(h, "act"), None

    if unroll_eager:
        n = jax.tree_util.tree_leaves(params["decoder"]["layers"])[0].shape[0]
        for i in range(n):
            h, _ = one(h, _seg_take(params["decoder"]["layers"], i))
    else:
        h, _ = jax.lax.scan(one, h, params["decoder"]["layers"])
    h = norm(cfg, params["decoder"]["final_norm"], h)
    logits = unembed(cfg, params, h)
    return logits, jnp.zeros((), jnp.float32)


def encdec_prefill(cfg: ModelConfig, params: Dict, frames: jax.Array,
                   tokens: jax.Array, max_len: int,
                   cache_dtype=jnp.bfloat16, unroll_eager: bool = False
                   ) -> Tuple[jax.Array, Dict]:
    """Encoder pass + decoder prefill. Cache holds self-KV and cross-KV."""
    enc = encode(cfg, params, frames, unroll_eager)
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    h = embed(params["embed"], tokens, dtype)
    h = h + sinusoidal_positions(s, cfg.d_model)[None].astype(dtype)
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)

    cross_dtype = _float_cache_dtype(cache_dtype)

    def one(h, p):
        lp = p["layer"]
        self_cache = attn.init_kv_cache(cfg, b, max_len, cache_dtype)
        hn = norm(cfg, lp["norm1"], h)
        y, self_cache = attn.attention_prefill(
            cfg, lp["mixer"], hn, positions, self_cache, name="layer.mixer")
        h = h + y
        hn = norm(cfg, p["xnorm"], h)
        kv = attn.cross_attention_kv(cfg, p["xattn"], enc, "xattn")
        h = h + attn.cross_attention(cfg, p["xattn"], hn, kv, "xattn")
        hn = norm(cfg, lp["norm2"], h)
        h = h + mlp(cfg, lp["mlp"], hn, name="layer.mlp")
        return h, {"self": self_cache,
                   "cross": jax.tree_util.tree_map(
                       lambda a: a.astype(cross_dtype), kv)}

    caches = []
    if unroll_eager:
        n = jax.tree_util.tree_leaves(params["decoder"]["layers"])[0].shape[0]
        for i in range(n):
            h, c = one(h, _seg_take(params["decoder"]["layers"], i))
            caches.append(c)
        cache = _stack_trees(caches)
    else:
        h, cache = jax.lax.scan(one, h, params["decoder"]["layers"])
    h = norm(cfg, params["decoder"]["final_norm"], h[:, -1:])
    logits = unembed(cfg, params, h)[:, 0]
    return logits, cache


def encdec_prefill_begin(cfg: ModelConfig, params: Dict, frames: jax.Array,
                         tokens: jax.Array, max_len: int,
                         cache_dtype=jnp.bfloat16, unroll_eager: bool = False
                         ) -> Tuple[jax.Array, Dict]:
    """Incremental enc-dec prefill setup: one encoder pass, cross-KV cached
    per layer, empty self-KV caches, decoder inputs embedded + positional."""
    enc = encode(cfg, params, frames, unroll_eager)
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    h = embed(params["embed"], tokens, dtype)
    h = h + sinusoidal_positions(s, cfg.d_model)[None].astype(dtype)

    cross_dtype = _float_cache_dtype(cache_dtype)

    def mk_cache(_, p):
        kv = attn.cross_attention_kv(cfg, p["xattn"], enc, "xattn")
        return 0, {"self": attn.init_kv_cache(cfg, b, max_len, cache_dtype),
                   "cross": jax.tree_util.tree_map(
                       lambda a: a.astype(cross_dtype), kv)}

    if unroll_eager:
        n = jax.tree_util.tree_leaves(params["decoder"]["layers"])[0].shape[0]
        cache = _stack_trees([mk_cache(0, _seg_take(
            params["decoder"]["layers"], i))[1] for i in range(n)])
    else:
        _, cache = jax.lax.scan(mk_cache, 0, params["decoder"]["layers"])
    return h, cache


def encdec_prefill_step(cfg: ModelConfig, params: Dict, h_chunk: jax.Array,
                        start: int, cache: Dict, unroll_eager: bool = False
                        ) -> Tuple[jax.Array, Dict]:
    """One decoder prefill chunk at positions [start, start+C)."""
    dtype = jnp.dtype(cfg.dtype)
    b, c, _ = h_chunk.shape
    positions = start + jnp.arange(c, dtype=jnp.int32)[None, :].repeat(b, 0)

    def one(h, xs):
        p, cc = xs
        lp = p["layer"]
        hn = norm(cfg, lp["norm1"], h)
        y, self_cache = attn.attention_prefill(
            cfg, lp["mixer"], hn, positions, cc["self"], name="layer.mixer",
            start=start)
        h = h + y
        hn = norm(cfg, p["xnorm"], h)
        h = h + attn.cross_attention(cfg, p["xattn"], hn,
                                     jax.tree_util.tree_map(
                                         lambda a: a.astype(dtype),
                                         cc["cross"]), "xattn")
        hn = norm(cfg, lp["norm2"], h)
        h = h + mlp(cfg, lp["mlp"], hn, name="layer.mlp")
        return h, {"self": self_cache, "cross": cc["cross"]}

    if unroll_eager:
        n = jax.tree_util.tree_leaves(params["decoder"]["layers"])[0].shape[0]
        ncs, h = [], h_chunk
        for i in range(n):
            h, nc = one(h, (_seg_take(params["decoder"]["layers"], i),
                            _seg_take(cache, i)))
            ncs.append(nc)
        return h, _stack_trees(ncs)
    return jax.lax.scan(one, h_chunk, (params["decoder"]["layers"], cache))


def encdec_prefill_finish(cfg: ModelConfig, params: Dict, h_last: jax.Array
                          ) -> jax.Array:
    h = norm(cfg, params["decoder"]["final_norm"], h_last[:, -1:])
    return unembed(cfg, params, h)[:, 0]


def encdec_prefill_chunked(cfg: ModelConfig, params: Dict, frames: jax.Array,
                           tokens: jax.Array, max_len: int, chunk: int,
                           cache_dtype=jnp.bfloat16,
                           unroll_eager: bool = False
                           ) -> Tuple[jax.Array, Dict]:
    """Chunked-decoder variant of :func:`encdec_prefill`: one encoder pass,
    cross-KV computed once, then the decoder prompt runs ``chunk`` positions
    at a time with self-attention continuing from cache (start offsets)."""
    assert chunk > 0
    h, cache = encdec_prefill_begin(cfg, params, frames, tokens, max_len,
                                    cache_dtype, unroll_eager)
    s = h.shape[1]
    hc = h[:, :0]
    for c0 in range(0, s, chunk):
        hc, cache = encdec_prefill_step(cfg, params,
                                        h[:, c0:min(s, c0 + chunk)], c0,
                                        cache, unroll_eager=unroll_eager)
    return encdec_prefill_finish(cfg, params, hc), cache


def encdec_decode_step(cfg: ModelConfig, params: Dict, token: jax.Array,
                       pos: jax.Array, cache: Dict,
                       unroll_eager: bool = False
                       ) -> Tuple[jax.Array, Dict]:
    dtype = jnp.dtype(cfg.dtype)
    b = token.shape[0]
    h = embed(params["embed"], token[:, None], dtype)
    # position embedding for the current slot (same table, gathered)
    tbl = sinusoidal_positions(cfg.max_seq_len, cfg.d_model).astype(dtype)
    h = h + tbl[pos][:, None, :]

    def one(h, xs):
        p, c = xs
        lp = p["layer"]
        hn = norm(cfg, lp["norm1"], h)
        y, self_cache = attn.attention_decode(cfg, lp["mixer"], hn, pos,
                                              c["self"], name="layer.mixer")
        h = h + y
        hn = norm(cfg, p["xnorm"], h)
        h = h + attn.cross_attention(cfg, p["xattn"], hn,
                                     jax.tree_util.tree_map(
                                         lambda a: a.astype(dtype),
                                         c["cross"]), "xattn")
        hn = norm(cfg, lp["norm2"], h)
        h = h + mlp(cfg, lp["mlp"], hn, name="layer.mlp")
        return h, {"self": self_cache, "cross": c["cross"]}

    if unroll_eager:
        n = jax.tree_util.tree_leaves(params["decoder"]["layers"])[0].shape[0]
        ncs = []
        for i in range(n):
            h, nc = one(h, (_seg_take(params["decoder"]["layers"], i),
                            _seg_take(cache, i)))
            ncs.append(nc)
        cache = _stack_trees(ncs)
    else:
        h, cache = jax.lax.scan(one, h, (params["decoder"]["layers"], cache))
    h = norm(cfg, params["decoder"]["final_norm"], h)
    logits = unembed(cfg, params, h)[:, 0]
    return logits, cache
