"""Batched serving engine.

``serve_step`` is the unit the dry-run lowers for decode shapes: one new
token for every sequence in the batch against a populated cache. The engine
wraps it in a greedy/temperature generation loop with a ragged-completion
mask (sequences finish independently; finished lanes keep decoding pad
tokens but their outputs are frozen — the standard static-shape batch
pattern).

EOS convention: the eos token itself is never emitted. The step that
samples eos writes token 0 / logprob 0.0 and marks the lane done, so
``tokens[b, :steps[b]]`` is exactly the usable output and a model that
legitimately generates token id 0 is not miscounted (``steps`` comes from
the done mask, not from ``tokens != 0``).

Weights may be full precision or int4-packed (``QuantizedTensor`` leaves,
produced by core/pipeline.quantize_model) — ``models.linear.dense``
dispatches per leaf, so the same step function serves both and the dry-run
can lower the quantized decode path explicitly (the paper's deployment
claim: §Perf compares both). The quantized matmul backend is selected by
``serve.w4a16_impl`` (kernels.ops.w4a16_default_impl trace-time context).

The static-batch loop here is the parity baseline; the continuous-batching
scheduler lives in serving/scheduler.py (docs/SERVING.md).
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import Config
from repro.core import faults
from repro.kernels import ops as kops
from repro.models import transformer as T


class GenResult(NamedTuple):
    tokens: jax.Array       # (B, max_new) generated ids (0 on done lanes)
    logprobs: jax.Array     # (B, max_new)
    steps: jax.Array        # (B,) tokens actually produced (pre-eos)


# -- failure accounting -------------------------------------------------------
#
# Every runtime degradation is counted, never silent (docs/SERVING.md
# "Failure handling"). The static generate() loop below and the continuous
# scheduler both funnel pallas→xla downgrades through this counter.

_ENGINE_STATS: Dict[str, int] = {"kernel_degradations": 0}


def engine_stats() -> Dict[str, int]:
    """Snapshot of engine-level failure counters (see also
    ``kernels.ops.fallback_stats`` for trace-time budget fallbacks)."""
    return dict(_ENGINE_STATS)


def _kernel_fault(e: Exception) -> bool:
    """Is this exception a kernel-path failure worth degrading over?

    Only an injected fault at the ``kernels.pallas_dispatch`` site is.
    Injected faults at other sites propagate to their own handlers, and a
    real exception (a Mosaic lowering or compile error, a shape bug)
    propagates to the caller: degrading over it would serve every request
    through the XLA reference while the kernel that should run on the
    device is broken, with nothing but a counter to show for it.
    """
    return getattr(e, "site", None) == "kernels.pallas_dispatch"


def decode_step_guarded(cfg: Config, params: Any, token: jax.Array,
                        pos: jax.Array, caches: Any
                        ) -> Tuple[jax.Array, jax.Array, Any]:
    """Greedy decode step with a fused finite-logits flag.

    Returns ``(next_token, ok, caches)`` where ``ok`` is a (B,) bool —
    False on any lane whose logits went non-finite (NaN/Inf poisoning, e.g.
    a corrupted KV lane). One dispatch, two (B,)-sized transfers: the
    quarantine check costs no extra logits round-trip.
    """
    lg, caches = serve_step(cfg, params, token, pos, caches)
    ok = jnp.all(jnp.isfinite(lg), axis=-1)
    return jnp.argmax(lg, axis=-1).astype(jnp.int32), ok, caches


def serve_step(cfg: Config, params: Any, token: jax.Array, pos: jax.Array,
               caches: Any) -> Tuple[jax.Array, Any]:
    """One decode step (the dry-run unit). token/pos: (B,)."""
    if cfg.model.is_encoder_decoder:
        return T.encdec_decode_step(cfg.model, params, token, pos, caches)
    return T.decode_step(cfg.model, params, token, pos, caches)


def cache_dtype(cfg: Config):
    """Decode-cache precision from ``serve.kv_cache``: the ``"int8"``
    string sentinel (quantized codes+scales leaves, models/attention.py)
    or bf16."""
    return "int8" if cfg.serve.kv_cache == "int8" else jnp.bfloat16


def prefill(cfg: Config, params: Any, batch: Dict[str, jax.Array],
            max_len: int) -> Tuple[jax.Array, Any]:
    """Prefill from a batch dict ({tokens, embeds?/frames?}).

    ``serve.prefill_chunk > 0`` runs the prompt through the blocks in
    chunks of that many positions (bounded per-step work for interleaving
    with decode); logits/caches match single-shot prefill.
    """
    chunk = cfg.serve.prefill_chunk
    cdt = cache_dtype(cfg)
    if cfg.model.is_encoder_decoder:
        if chunk > 0:
            return T.encdec_prefill_chunked(cfg.model, params,
                                            batch["frames"], batch["tokens"],
                                            max_len, chunk, cache_dtype=cdt)
        return T.encdec_prefill(cfg.model, params, batch["frames"],
                                batch["tokens"], max_len, cache_dtype=cdt)
    if chunk > 0:
        return T.prefill_chunked(cfg.model, params, batch["tokens"], max_len,
                                 chunk, embeds=batch.get("embeds"),
                                 cache_dtype=cdt)
    return T.prefill(cfg.model, params, batch["tokens"], max_len,
                     embeds=batch.get("embeds"), cache_dtype=cdt)


def prefill_begin(cfg: Config, params: Any, batch: Dict[str, jax.Array],
                  max_len: int) -> Tuple[jax.Array, Any]:
    """Incremental prefill setup (continuous batching): returns the full
    embedded input ``h`` and empty caches; feed ``h`` slices through
    :func:`prefill_step` one chunk at a time."""
    cdt = cache_dtype(cfg)
    if cfg.model.is_encoder_decoder:
        return T.encdec_prefill_begin(cfg.model, params, batch["frames"],
                                      batch["tokens"], max_len,
                                      cache_dtype=cdt)
    return T.prefill_begin(cfg.model, params, batch["tokens"], max_len,
                           embeds=batch.get("embeds"), cache_dtype=cdt)


def prefill_step(cfg: Config, params: Any, h_chunk: jax.Array, start: int,
                 caches: Any) -> Tuple[jax.Array, Any]:
    """One prefill chunk occupying positions [start, start + C)."""
    if cfg.model.is_encoder_decoder:
        return T.encdec_prefill_step(cfg.model, params, h_chunk, start,
                                     caches)
    return T.prefill_step(cfg.model, params, h_chunk, start, caches)


def prefill_finish(cfg: Config, params: Any, h_last: jax.Array) -> jax.Array:
    """Next-token logits from the final chunk's output."""
    if cfg.model.is_encoder_decoder:
        return T.encdec_prefill_finish(cfg.model, params, h_last)
    return T.prefill_finish(cfg.model, params, h_last)


def _sample(key: jax.Array, logits: jax.Array, temperature: float
            ) -> jax.Array:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def generate(cfg: Config, params: Any, batch: Dict[str, jax.Array], *,
             max_new_tokens: Optional[int] = None, eos_id: int = -1,
             temperature: Optional[float] = None,
             seed: int = 0) -> GenResult:
    """Greedy/temperature generation. Static shapes; jit-compiled loop.

    An injected kernel fault on a pallas path (w4a16 matmul or the fused
    int8-KV attention) degrades this call to the xla reference backends and
    retries once — counted in ``engine_stats()``, never silent. Any other
    error propagates.
    """
    impl = cfg.serve.w4a16_impl
    kv_impl = cfg.serve.kv_impl
    try:
        with kops.w4a16_default_impl(impl), \
                kops.kv_attn_default_impl(kv_impl):
            return _generate(cfg, params, batch,
                             max_new_tokens=max_new_tokens, eos_id=eos_id,
                             temperature=temperature, seed=seed)
    except Exception as e:                      # noqa: BLE001 — classified
        if (impl == "xla" and kv_impl == "xla") or not _kernel_fault(e):
            raise
        _ENGINE_STATS["kernel_degradations"] += 1
        warnings.warn(f"kernel fault ({e!r}): degrading generate() "
                      "to impl='xla'", RuntimeWarning, stacklevel=2)
        with kops.w4a16_default_impl("xla"), \
                kops.kv_attn_default_impl("xla"):
            return _generate(cfg, params, batch,
                             max_new_tokens=max_new_tokens, eos_id=eos_id,
                             temperature=temperature, seed=seed)


def _generate(cfg: Config, params: Any, batch: Dict[str, jax.Array], *,
              max_new_tokens: Optional[int], eos_id: int,
              temperature: Optional[float], seed: int) -> GenResult:
    sc = cfg.serve
    mnt = max_new_tokens or sc.max_new_tokens
    temp = sc.temperature if temperature is None else temperature
    b, s0 = batch["tokens"].shape
    n_front = batch["embeds"].shape[1] if batch.get("embeds") is not None \
        else 0
    max_len = s0 + n_front + mnt + 1
    logits, caches = prefill(cfg, params, batch, max_len)

    def body(carry, i):
        token, pos, caches, done, key = carry
        key, sub = jax.random.split(key)
        lg, caches = serve_step(cfg, params, token, pos, caches)
        raw = _sample(sub, lg, temp)
        lp = jax.nn.log_softmax(lg)[jnp.arange(b), raw]
        newly_done = done | (raw == eos_id)
        nxt = jnp.where(newly_done, 0, raw)
        out = (nxt, jnp.where(newly_done, 0.0, lp), ~newly_done)
        return (nxt, pos + 1, caches, newly_done, key), out

    first_raw = _sample(jax.random.PRNGKey(seed), logits, temp)
    done0 = first_raw == eos_id
    first = jnp.where(done0, 0, first_raw)
    lp0 = jnp.where(done0, 0.0,
                    jax.nn.log_softmax(logits)[jnp.arange(b), first_raw])
    pos0 = jnp.full((b,), s0 + n_front, jnp.int32)
    carry = (first, pos0, caches, done0, jax.random.PRNGKey(seed + 1))
    if mnt > 1:
        carry, (toks, lps, valid) = jax.lax.scan(body, carry,
                                                 jnp.arange(mnt - 1))
        tokens = jnp.concatenate([first[:, None], toks.T], axis=1)
        logprobs = jnp.concatenate([lp0[:, None], lps.T], axis=1)
        steps = (~done0).astype(jnp.int32) + \
            jnp.sum(valid.astype(jnp.int32), axis=0)
    else:
        tokens, logprobs = first[:, None], lp0[:, None]
        steps = (~done0).astype(jnp.int32)
    return GenResult(tokens, logprobs, steps)
