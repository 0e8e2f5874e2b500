"""Shared benchmark helpers: train a small LM, evaluate PPL/accuracy."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import Config
from repro.configs import get_config
from repro.data import MarkovLM, SentimentTask, calibration_batches
from repro.models import attention as A
from repro.models import transformer as T
from repro.training.train_step import init_train_state, make_train_step


def bench_config(arch: str = "opt-proxy", **model_over) -> Config:
    cfg = get_config(arch, smoke=True)
    for k, v in model_over.items():
        setattr(cfg.model, k, v)
    cfg.model.__post_init__()
    return cfg


def train_lm(cfg: Config, steps: int = 80, lr: float = 3e-3,
             batch: int = 8, seq: int = 32, seed: int = 0,
             mix_sentiment: bool = True):
    """Train on the Markov stream (+ sentiment batches so the downstream
    task is in-distribution, like the paper's instruction-tuned models)."""
    cfg.train.lr = lr
    cfg.train.warmup_steps = max(2, steps // 10)
    cfg.train.steps = steps
    st = init_train_state(cfg, jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(cfg))
    lm = MarkovLM(cfg.model.vocab_size, seed=seed, branching=3)
    sent = SentimentTask(cfg.model.vocab_size, seed=seed)
    for i in range(steps):
        if mix_sentiment and i % 3 == 2:
            b, _ = sent.batch(batch, seq)
        else:
            b = lm.batch(batch, seq)
        st, m = step(st, b)
    return st.params, lm, sent


def eval_ppl(cfg: Config, params, lm: MarkovLM, n: int = 4, batch: int = 8,
             seq: int = 32) -> float:
    lm_eval = MarkovLM(cfg.model.vocab_size, seed=lm.seed, branching=3)
    lm_eval.step = 50_000
    tot, cnt = 0.0, 0
    for _ in range(n):
        toks = lm_eval.batch(batch, seq)["tokens"]
        logits, _ = T.forward(cfg.model, params, toks)
        logz = jax.nn.logsumexp(logits[:, :-1], axis=-1)
        gold = jnp.take_along_axis(logits[:, :-1], toks[:, 1:, None],
                                   axis=-1)[..., 0]
        tot += float(jnp.sum(logz - gold))
        cnt += int(toks[:, 1:].size)
    return float(np.exp(tot / cnt))


def eval_sentiment(cfg: Config, params, sent: SentimentTask,
                   n: int = 128, seq: int = 24) -> float:
    ev = SentimentTask(cfg.model.vocab_size, seed=sent.seed)
    ev.step = 50_000
    batch, labels = ev.batch(n, seq)
    logits, _ = T.forward(cfg.model, params, batch["tokens"])
    return ev.accuracy(logits[:, -2], labels)


def make_calib(cfg: Config, lm: MarkovLM, n_batches: int = 4,
               batch: int = 8, seq: int = 32):
    src = MarkovLM(cfg.model.vocab_size, seed=lm.seed, branching=3)
    return calibration_batches(src, n_batches, batch, seq)


def param_bytes(params) -> int:
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(params)
               if hasattr(l, "dtype"))


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(tree))


def cache_bytes_per_seq(mc, max_len: int, cache_dtype) -> int:
    """Decode-cache bytes for ONE sequence at capacity ``max_len``, measured
    via ``jax.eval_shape`` over the real cache constructors — the same
    layouts the engines allocate, nothing materialized. ``cache_dtype`` is a
    jnp dtype or the ``"int8"`` sentinel; for int8 the codes, per-block
    scales and error-feedback accumulators are all counted, and leaves the
    sentinel keeps in float (MLA latents, recurrent states, enc-dec
    cross-KV) are counted at their actual precision."""
    if mc.is_encoder_decoder:
        self_b = _tree_bytes(jax.eval_shape(
            lambda: A.init_kv_cache(mc, 1, max_len, cache_dtype)))
        cross_dtype = jnp.dtype(T._float_cache_dtype(cache_dtype))
        cross_b = 2 * mc.encoder_seq_len * mc.num_kv_heads * mc.head_dim \
            * cross_dtype.itemsize
        return mc.num_layers * (self_b + cross_b)
    return _tree_bytes(jax.eval_shape(
        lambda: T.init_block_caches(mc, 1, max_len, cache_dtype)))
