"""GPipe pipeline parallelism over the ``pod`` axis (shard_map + ppermute).

At 2+ pods the ``pod`` axis crosses DCN; instead of FSDP/TP traffic per
layer, PP sends only microbatch boundary activations between pods — the
classic reason to pipeline across slow links. This module implements
schedule-level GPipe:

  - the layer stack is split into ``n_stages`` contiguous stages, one per
    pod-axis index; every device holds only its stage's parameters
    (stage-stacked leaves sharded on the leading stage dim);
  - a microbatch loop runs stages in lockstep: at tick ``t`` stage ``s``
    processes microbatch ``t − s`` (bubble fraction ``(S−1)/(T+S−1)``);
  - boundary activations move stage→stage+1 with ``lax.ppermute``.

The dry-run proves this lowers and partitions on the (pod, data, model)
mesh; tests/test_pipeline_parallel.py checks numeric equivalence of the
2-stage pipeline against the plain stacked forward on a CPU mesh.

This is the explicit-collective path; the default train config uses GSPMD
(DP×TP×FSDP) which XLA schedules with overlap. PP is the beyond-paper
option for DCN-limited multi-pod scaling (EXPERIMENTS.md §Perf discusses
when each wins).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def gpipe_forward(mesh: Mesh, stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stage_params: Any, x: jax.Array, *,
                  n_microbatches: int, axis: str = "pod") -> jax.Array:
    """Run ``stage_fn`` as a GPipe pipeline over ``axis``.

    stage_params: pytree with leading (n_stages,) dim on every leaf (sharded
    over ``axis``). x: (B, ...) global batch (sharded over ``axis`` is NOT
    required; microbatching happens on the leading dim).
    Returns stage_{S-1}(…stage_0(x)) for the full batch.
    """
    n_stages = mesh.shape[axis]
    assert x.shape[0] % n_microbatches == 0
    mb = x.shape[0] // n_microbatches

    def body(params_local, x_local):
        # params_local: this stage's params (leading dim 1) ; x_local: full x
        params_me = jax.tree_util.tree_map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        micro = x_local.reshape(n_microbatches, mb, *x_local.shape[1:])
        n_ticks = n_microbatches + n_stages - 1
        buf = jnp.zeros_like(micro[0])
        outs = jnp.zeros_like(micro)

        def tick(t, carry):
            buf, outs = carry
            # stage s works on microbatch t - s when 0 <= t-s < n_micro
            m_idx = t - s
            active = (m_idx >= 0) & (m_idx < n_microbatches)
            x_in = jnp.where(s == 0,
                             micro[jnp.clip(m_idx, 0, n_microbatches - 1)],
                             buf)
            y = stage_fn(params_me, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            outs = jax.lax.cond(
                active & (s == n_stages - 1),
                lambda o: o.at[jnp.clip(m_idx, 0, n_microbatches - 1)].set(y),
                lambda o: o, outs)
            # everyone passes forward (ring; the wrap-around is ignored)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast via psum of the
        # masked buffer (other stages contribute zeros)
        outs = jnp.where(s == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis)
        return outs.reshape(x_local.shape)

    other_axes = [a for a in mesh.axis_names if a != axis]
    pspec = P(axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(*([None] * x.ndim))),
        out_specs=P(*([None] * x.ndim)),
        check_vma=False,
    )(stage_params, x)


def stack_stages(layer_params_list, n_stages: int):
    """Group per-layer params into ``n_stages`` stage-stacked pytrees.

    Layers must divide evenly; each stage applies its chunk sequentially.
    """
    n = len(layer_params_list)
    assert n % n_stages == 0, (n, n_stages)
    per = n // n_stages
    stages = []
    for s in range(n_stages):
        chunk = layer_params_list[s * per:(s + 1) * per]
        stages.append(jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *chunk))
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stages), per


def make_stage_fn(layer_apply: Callable[[Any, jax.Array], jax.Array],
                  per_stage: int):
    """stage_fn scanning ``per_stage`` stacked layers."""

    def stage_fn(stage_params, x):
        def one(h, lp):
            return layer_apply(lp, h), None
        y, _ = jax.lax.scan(one, x, stage_params)
        return y

    return stage_fn
