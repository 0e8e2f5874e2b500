"""Float weights of an OPT-proxy model, made on the device from the seed.

Both sides of a cell read these functions: the program is handed what they
make (packed for serving, or as the float model to quantize), and the plain
reference (``bench/reference.py``) makes the same float weights again from
the same seed after the window. Nothing here imports the program.

Every leaf is drawn from ``fold_in(key, layer)`` and a fixed leaf order, so
layer ``l`` is the same whether it is made alone or inside a loop over all
layers. The distributions stand in for a trained OPT checkpoint:

- linear weights ``N(0, 1/in)`` (the attention output and the MLP down
  projection as well), biases ``N(0, 0.02)``;
- LayerNorm gains lognormal (sigma 0.5) and biases ``N(0, 0.02)``: OPT's
  activations have channels far larger than the rest, and a calibration
  Hessian built from equal channels would make GPTQ no better than
  rounding, which no real checkpoint does;
- the (tied) token embedding ``N(0, 0.02)``, OPT's initialisation scale.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

LN_GAIN_SIGMA = 0.5
BIAS_STD = 0.02
EMBED_STD = 0.02

# (parent, leaf, in_dim key, out_dim key) in the order leaves are drawn
_LINEARS = (("mixer", "q", "d", "d"), ("mixer", "k", "d", "d"),
            ("mixer", "v", "d", "d"), ("mixer", "o", "d", "d"),
            ("mlp", "up", "d", "f"), ("mlp", "down", "f", "d"))


class Dims(NamedTuple):
    """The sizes of an OPT-proxy configuration file's ``model`` section
    that the benchmark's own code needs (hashable: a static jit argument)."""
    num_layers: int
    d_model: int
    num_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float

    @classmethod
    def of(cls, model: Dict) -> "Dims":
        heads = model["num_heads"]
        return cls(model["num_layers"], model["d_model"], heads,
                   model.get("head_dim") or model["d_model"] // heads,
                   model["d_ff"], model["vocab_size"],
                   float(model.get("rope_theta", 10000.0)))


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one above 2**32."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _dims(mc) -> Dict[str, int]:
    return {"d": mc.d_model, "f": mc.d_ff}


def _norm(key: jax.Array, d: int) -> Dict[str, jax.Array]:
    kg, kb = jax.random.split(key)
    return {"scale": jnp.exp(LN_GAIN_SIGMA * jax.random.normal(kg, (d,))),
            "bias": BIAS_STD * jax.random.normal(kb, (d,))}


def layer_params(mc, key: jax.Array) -> Dict:
    """One transformer layer, in the program's param-tree layout
    (``norm1``, ``mixer.{q,k,v,o}``, ``norm2``, ``mlp.{up,down}``; each
    linear ``{"w": (in, out), "b": (out,)}``), all float32."""
    dims = _dims(mc)
    keys = jax.random.split(key, 2 + 2 * len(_LINEARS))
    p: Dict = {"norm1": _norm(keys[0], mc.d_model),
               "norm2": _norm(keys[1], mc.d_model),
               "mixer": {}, "mlp": {}}
    for i, (parent, leaf, din, dout) in enumerate(_LINEARS):
        n_in, n_out = dims[din], dims[dout]
        w = jax.random.normal(keys[2 + 2 * i], (n_in, n_out)) * n_in ** -0.5
        b = BIAS_STD * jax.random.normal(keys[3 + 2 * i], (n_out,))
        p[parent][leaf] = {"w": w, "b": b}
    return p


def layer_key(seed_key: jax.Array, layer: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), layer)


def embed_and_final_norm(mc, seed_key: jax.Array):
    """(embedding (V, D), final LayerNorm) of the tied-embedding model."""
    ke, kn = jax.random.split(jax.random.fold_in(seed_key, 2))
    emb = EMBED_STD * jax.random.normal(ke, (mc.vocab_size, mc.d_model))
    return emb, _norm(kn, mc.d_model)


def float_params(mc, seed_key: jax.Array) -> Dict:
    """The whole float model in the program's layout: ``blocks`` is one
    segment whose ``sub0`` leaves are stacked over the layers. Meant to be
    called under ``jax.jit``: sizes up to OPT-125m's fit one chip in f32."""
    layers = [layer_params(mc, layer_key(seed_key, i))
              for i in range(mc.num_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    emb, fnorm = embed_and_final_norm(mc, seed_key)
    return {"embed": {"embedding": emb}, "blocks": [{"sub0": stacked}],
            "final_norm": fnorm}
