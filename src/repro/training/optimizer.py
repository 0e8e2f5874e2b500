"""AdamW with optional int8 block-quantized moments.

The int8 moments are the Dettmers-style distributed-optimization trick the
paper's own method echoes (block-wise quantization): each moment tensor is
flattened into blocks of 128, stored int8 with a per-block f32 absmax
scale — 4× smaller optimizer state. Moments are dequantized, updated in
f32, and requantized every step; the quantization noise on m/v is well
inside Adam's own noise floor (tested against exact AdamW in
tests/test_training.py).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.config import TrainConfig

_BLOCK = 128


class Quantized8(NamedTuple):
    q: jax.Array        # int8 payload, padded flat (n_blocks, _BLOCK)
    scale: jax.Array    # (n_blocks,) f32 absmax / 127
    # static shape restored from the paired param


def _q8(x: jax.Array) -> Quantized8:
    flat = x.reshape(-1)
    n = flat.shape[0]
    nb = -(-n // _BLOCK)
    flat = jnp.pad(flat, (0, nb * _BLOCK - n)).reshape(nb, _BLOCK)
    scale = jnp.max(jnp.abs(flat), axis=1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(flat / scale[:, None]), -127, 127).astype(jnp.int8)
    return Quantized8(q, scale.astype(jnp.float32))


def _dq8(z: Quantized8, shape: Tuple[int, ...]) -> jax.Array:
    flat = (z.q.astype(jnp.float32) * z.scale[:, None]).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape)


class AdamWState(NamedTuple):
    step: jax.Array
    m: Any              # pytree of f32 arrays or Quantized8
    v: Any


def adamw_init(params: Any, int8: bool = False) -> AdamWState:
    def zero(p):
        z = jnp.zeros(p.shape, jnp.float32)
        return _q8(z) if int8 else z
    # m and v must be distinct buffers (the train step donates its input
    # state; aliased leaves would be donated twice)
    return AdamWState(jnp.zeros((), jnp.int32),
                      jax.tree_util.tree_map(zero, params),
                      jax.tree_util.tree_map(zero, params))


def adamw_update(grads: Any, state: AdamWState, params: Any, *,
                 lr: jax.Array, tc: TrainConfig,
                 int8: bool = False) -> Tuple[Any, AdamWState]:
    step = state.step + 1
    b1, b2, eps, wd = tc.beta1, tc.beta2, tc.eps, tc.weight_decay
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        mf = _dq8(m, p.shape) if int8 else m
        vf = _dq8(v, p.shape) if int8 else v
        mf = b1 * mf + (1 - b1) * g
        vf = b2 * vf + (1 - b2) * g * g
        mh = mf / c1
        vh = vf / c2
        delta = mh / (jnp.sqrt(vh) + eps) + wd * p.astype(jnp.float32)
        newp = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        return newp, (_q8(mf) if int8 else mf), (_q8(vf) if int8 else vf)

    is_q8 = lambda x: isinstance(x, Quantized8)
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = jax.tree_util.tree_leaves(state.m, is_leaf=is_q8) \
        if int8 else treedef.flatten_up_to(state.m)
    flat_v = jax.tree_util.tree_leaves(state.v, is_leaf=is_q8) \
        if int8 else treedef.flatten_up_to(state.v)
    outs = [upd(p, g, m, v) for p, g, m, v in
            zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in outs])
    new_m = treedef.unflatten([o[1] for o in outs])
    new_v = treedef.unflatten([o[2] for o in outs])
    return new_p, AdamWState(step, new_m, new_v)


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_by_global_norm(tree: Any, max_norm: float) -> Tuple[Any, jax.Array]:
    gn = global_norm(tree)
    factor = jnp.minimum(1.0, max_norm / (gn + 1e-9))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * factor).astype(g.dtype),
        tree), gn
