"""The plain float32 reference for the quantize cell's correctness check.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, written from
the OPT-proxy block equations, the GPTQ paper and the RPIQ paper's stage-2
equations, importing nothing of the program. Weights come from
``bench/weights.py`` and the seed, never from what the program made.

The OPT-proxy block (pre-LayerNorm, biases everywhere)::

    a = LN1(h);  q, k, v = a Wq + bq, a Wk + bk, a Wv + bv
    q, k = RoPE(q), RoPE(k)          # half-split rotation, theta 10000
    h = h + softmax(q k^T / sqrt(hd) + causal) v Wo + bo
    h = h + GELU(LN2(h) Wup + bup) Wdown + bdown

Departures from facebook/opt, shared with the program and listed under
``assumed`` in the configuration file: GELU (tanh form) where OPT has
ReLU, RoPE where OPT learns positions.

The quantizer, layer by layer along its own calibration stream: capture
each linear's inputs, GPTQ (stage 1) on their Hessian, the closed-loop
stage 2 on the last calibration batch (the single instance), and the
next layer's inputs from the quantized layer. ``dtype_name="bf16"`` runs
the whole chain in bfloat16: the control, one precision below the
quantizer's float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST

LINEARS = (("mixer", "q"), ("mixer", "k"), ("mixer", "v"), ("mixer", "o"),
           ("mlp", "up"), ("mlp", "down"))


class Stage2(NamedTuple):
    """How stage 2 runs, from the configuration file's ``quant`` section."""
    block: int              # columns a Gauss-Seidel block updates at once
    alpha: float            # damped step toward the projected solution
    rounds: int             # rounds at most
    early_stop: bool        # stop once the residual stops falling

    @classmethod
    def of(cls, quant: Dict) -> "Stage2":
        if not quant["rpiq_use_global_hessian"]:
            raise ValueError("the reference's stage 2 takes its curvature "
                             "from the calibration Hessian only")
        return cls(int(quant["blocksize"]), float(quant["rpiq_alpha"]),
                   int(quant["rpiq_iters"]), bool(quant["rpiq_early_stop"]))


# ---------------------------------------------------------------------------
# int4 artifacts
# ---------------------------------------------------------------------------

def unpack_codes(packed: np.ndarray) -> np.ndarray:
    """(out, in/2) uint8, low nibble = even column → (out, in) codes."""
    lo = packed & 0x0F
    hi = (packed >> 4) & 0x0F
    return np.stack([lo, hi], axis=-1).reshape(packed.shape[0], -1)


def dequant_packed(packed, scales, zeros, group: int) -> np.ndarray:
    """The dequantized (out, in) float32 weights an int4 artifact holds."""
    q = unpack_codes(np.asarray(packed)).astype(np.float32)
    s = np.repeat(np.asarray(scales, np.float32), group, axis=1)
    z = np.repeat(np.asarray(zeros, np.float32), group, axis=1)
    return (q - z) * s


def rtn_dequant(w_io: jax.Array, bits: int, group: int) -> jax.Array:
    """(in, out) float → (in, out) round-to-nearest weights on per-(row,
    group) min-max grids that hold 0."""
    w_oi = w_io.T
    o, i = w_oi.shape
    g = w_oi.reshape(o, i // group, group)
    qmax = 2.0 ** bits - 1.0
    scale = jnp.maximum((jnp.maximum(g.max(-1), 0.0)
                         - jnp.minimum(g.min(-1), 0.0)) / qmax, 1e-8)
    zero = jnp.clip(jnp.round(-jnp.minimum(g.min(-1), 0.0) / scale), 0.0,
                    qmax)
    s = jnp.repeat(scale, group, axis=1)
    z = jnp.repeat(zero, group, axis=1)
    q = jnp.clip(jnp.round(w_oi / s) + z, 0.0, qmax)
    return ((q - z) * s).T


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------

def _cast(x: jax.Array, act_dtype: str) -> jax.Array:
    if act_dtype == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _mm(x, w, act_dtype):
    return jnp.matmul(_cast(x, act_dtype), w, precision=HIGHEST)


def layer_norm(x, p, eps: float = 1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta: float):
    """x: (S, H, hd); the rotation pairs dim j with dim j + hd/2."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(mc, p, a, act_dtype: str = "f32"):
    """Causal self-attention of one sequence a: (S, D). Returns the layer
    output and the heads' output (the input of ``o``), both (S, D)."""
    s = a.shape[0]
    h, hd = mc.num_heads, mc.head_dim
    q = (_mm(a, p["q"]["w"], act_dtype) + p["q"]["b"]).reshape(s, h, hd)
    k = (_mm(a, p["k"]["w"], act_dtype) + p["k"]["b"]).reshape(s, h, hd)
    v = (_mm(a, p["v"]["w"], act_dtype) + p["v"]["b"]).reshape(s, h, hd)
    pos = jnp.arange(s)
    q, k = rope(q, pos, mc.rope_theta), rope(k, pos, mc.rope_theta)
    sc = jnp.einsum("qhd,khd->hqk", _cast(q, act_dtype), _cast(k, act_dtype),
                    precision=HIGHEST) / math.sqrt(hd)
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _cast(pr, act_dtype), _cast(v, act_dtype),
                   precision=HIGHEST).reshape(s, h * hd)
    return _mm(o, p["o"]["w"], act_dtype) + p["o"]["b"], o


def block(mc, p, h, act_dtype: str = "f32", taps: Dict | None = None):
    """One layer on one sequence h: (S, D). ``taps`` collects the input
    of each linear (the calibration capture)."""
    a = layer_norm(h, p["norm1"])
    y, o = attention(mc, p["mixer"], a, act_dtype)
    h = h + y
    m = layer_norm(h, p["norm2"])
    u = gelu_tanh(_mm(m, p["mlp"]["up"]["w"], act_dtype) + p["mlp"]["up"]["b"])
    if taps is not None:
        taps.update(q=a, k=a, v=a, o=o, up=m, down=u)
    return h + _mm(u, p["mlp"]["down"]["w"], act_dtype) + p["mlp"]["down"]["b"]


# ---------------------------------------------------------------------------
# Stage 1: GPTQ
# ---------------------------------------------------------------------------

def gptq(w_oi: jax.Array, hess: jax.Array, bits: int, group: int,
         percdamp: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """GPTQ (Frantar et al. 2022), one column at a time: quantize column
    j on its group's grid (taken from the error-compensated weights when j
    opens a group), and spread the rounding error over the columns right
    of j through the upper Cholesky factor of the damped inverse Hessian.
    Returns the dequantized (out, in) weights and the grid's (out,
    in/group) scales and integer zeros."""
    o, i = w_oi.shape
    dt = w_oi.dtype
    hd = hess + percdamp * jnp.mean(jnp.diag(hess)) * jnp.eye(i, dtype=dt)
    hinv = jnp.linalg.inv(hd.astype(jnp.float32))
    u = jnp.linalg.cholesky(hinv).T.astype(dt)          # upper factor
    qmax = 2.0 ** bits - 1.0
    cols = jnp.arange(i)

    def col(j, carry):
        w, out, scale, zero, scales, zeros = carry
        g0 = (j // group) * group
        wg = jax.lax.dynamic_slice(w, (0, g0), (o, group))
        new_s = jnp.maximum((jnp.maximum(wg.max(1), 0.0)
                             - jnp.minimum(wg.min(1), 0.0)) / qmax, 1e-8)
        new_z = jnp.clip(jnp.round(-jnp.minimum(wg.min(1), 0.0) / new_s),
                         0.0, qmax)
        opens = j % group == 0
        scale = jnp.where(opens, new_s.astype(dt), scale)
        zero = jnp.where(opens, new_z.astype(dt), zero)
        scales = jax.lax.dynamic_update_slice(scales, scale[:, None],
                                              (0, j // group))
        zeros = jax.lax.dynamic_update_slice(zeros, zero[:, None],
                                             (0, j // group))
        wj = jax.lax.dynamic_slice(w, (0, j), (o, 1))[:, 0]
        qj = (jnp.clip(jnp.round(wj / scale) + zero, 0.0, qmax)
              - zero) * scale
        err = (wj - qj) / u[j, j]
        right = jnp.where(cols > j, u[j], 0.0).astype(dt)
        w = w - err[:, None] * right[None, :]
        out = jax.lax.dynamic_update_slice(out, qj[:, None], (0, j))
        return w, out, scale, zero, scales, zeros

    grid = jnp.zeros((o, i // group), dt)
    init = (w_oi, jnp.zeros_like(w_oi), jnp.ones((o,), dt),
            jnp.zeros((o,), dt), grid, grid)
    _, out, _, _, scales, zeros = jax.lax.fori_loop(0, i, col, init)
    return out, scales, zeros


# ---------------------------------------------------------------------------
# Stage 2: the closed loop on the single instance
# ---------------------------------------------------------------------------

def stage2(w_fp: jax.Array, w1: jax.Array, scales: jax.Array,
           zeros: jax.Array, x: jax.Array, curvature: jax.Array, bits: int,
           group: int, s2: Stage2):
    """RPIQ stage 2 (paper eq. 2-8, 12-14, 19-23) for one linear.

    ``w_fp``/``w1`` (out, in): the float and the stage-1 weights;
    ``scales``/``zeros`` the stage-1 grid; ``x`` (n, in) the instance's
    inputs; ``curvature`` (in, in) the damped calibration Hessian scaled
    to the instance's n rows. A round sweeps the column blocks in order
    (Gauss-Seidel): block i's least-squares fit to the output residual
    with every other block's current contribution in place, solved with
    the curvature's diagonal block, projected onto the stage-1 grid, and
    a step of ``alpha`` toward it. After each round the whole iterate is
    projected onto the grid, and the best projection so far is kept; the
    loop stops when the residual of the iterate stops falling.

    Returns (kept weights (out, in), residual ``|Y - X W^T|^2`` before the
    first round and after each round run (NaN after a stop), rounds run).
    """
    o, i = w1.shape
    n = x.shape[0]
    bs = s2.block
    nblk = i // bs
    qmax = 2.0 ** bits - 1.0
    s_col = jnp.repeat(scales, group, axis=1)
    z_col = jnp.repeat(zeros, group, axis=1)

    def project(b, s, z):
        return (jnp.clip(jnp.round(b / s) + z, 0.0, qmax) - z) * s

    def mm(a, b):
        return jnp.matmul(a, b, precision=HIGHEST)

    y = mm(x, w_fp.T)                                    # (n, out)

    def residual(yq):
        r = y - yq
        return jnp.sum(r * r)

    blocks = jnp.stack([curvature[b * bs:(b + 1) * bs, b * bs:(b + 1) * bs]
                        for b in range(nblk)])
    inv = jnp.linalg.inv(blocks.astype(jnp.float32)).astype(w1.dtype)

    def one_block(b, carry):
        w, yq = carry
        c0 = b * bs
        wb = jax.lax.dynamic_slice(w, (0, c0), (o, bs))
        xb = jax.lax.dynamic_slice(x, (0, c0), (n, bs))
        own = mm(xb, wb.T)
        target = y - (yq - own)              # the rest of the layer in place
        fit = mm(inv[b], mm(xb.T, target)).T                 # (out, bs)
        on_grid = project(fit, jax.lax.dynamic_slice(s_col, (0, c0), (o, bs)),
                          jax.lax.dynamic_slice(z_col, (0, c0), (o, bs)))
        wb_new = wb + s2.alpha * (on_grid - wb)
        yq = yq - own + mm(xb, wb_new.T)
        return jax.lax.dynamic_update_slice(w, wb_new, (0, c0)), yq

    def one_round(t, carry):
        w, yq, best, best_loss, history, stopped, rounds = carry
        w_new, yq_new = jax.lax.fori_loop(0, nblk, one_block, (w, yq))
        g = residual(yq_new)
        cand = project(w_new, s_col, z_col)
        c_loss = residual(mm(x, cand.T))
        live = jnp.logical_not(stopped)
        better = jnp.logical_and(live, c_loss < best_loss)
        if s2.early_stop:
            stopped = jnp.logical_or(
                stopped, jnp.logical_and(live, g >= history[t] * (1.0 - 1e-6)))
        return (jnp.where(live, w_new, w), jnp.where(live, yq_new, yq),
                jnp.where(better, cand, best),
                jnp.where(better, c_loss, best_loss),
                history.at[t + 1].set(jnp.where(live, g, jnp.nan)),
                stopped, rounds + live.astype(jnp.int32))

    yq = mm(x, w1.T)
    g0 = residual(yq)
    history = jnp.full((s2.rounds + 1,), jnp.nan, g0.dtype).at[0].set(g0)
    init = (w1, yq, w1, g0, history, jnp.asarray(False),
            jnp.zeros((), jnp.int32))
    _, _, best, _, history, _, rounds = jax.lax.fori_loop(
        0, s2.rounds, one_round, init)
    return best, history, rounds


def proxy_error(w_oi, wq_oi, hess) -> jax.Array:
    """Relative output error on the calibration inputs:
    tr(dW H dW^T) / tr(W H W^T), H = X^T X / n."""
    dw = (w_oi - wq_oi).astype(jnp.float32)
    w32 = w_oi.astype(jnp.float32)
    num = jnp.sum(jnp.matmul(dw, hess, precision=HIGHEST) * dw)
    den = jnp.sum(jnp.matmul(w32, hess, precision=HIGHEST) * w32)
    return num / den


# ---------------------------------------------------------------------------
# The chain
# ---------------------------------------------------------------------------

class Linear(NamedTuple):
    """One linear of the reference's chain; every matrix (out, in) f32."""
    w: jax.Array            # the float weights
    wq: jax.Array           # after stage 2: what the chain keeps
    w1: jax.Array           # after stage 1
    hess: jax.Array         # X^T X / n over the whole calibration set
    scales: jax.Array       # (out, in/group) stage-1 grid
    zeros: jax.Array
    residual: jax.Array     # stage 2's residual history (rounds + 1,)
    rounds: jax.Array       # stage 2's rounds run


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6, 7, 8))
def _quant_layer_step(mc, p, hs, bits, group, percdamp, s2, instance,
                      dtype_name):
    """Capture one layer on the reference's own stream, quantize each
    linear (stage 1, then stage 2 on the last ``instance`` sequences),
    and propagate through the quantized layer.
    Returns (next stream, {linear: Linear})."""
    dt = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    act = "bf16" if dtype_name == "bf16" else "f32"

    def tap(h):
        t: Dict = {}
        block(mc, p, h, act, taps=t)
        return t
    taps = jax.lax.map(tap, hs)                  # each (n_seq, S, in)
    out = {}
    q = {"mixer": {}, "mlp": {}}
    for parent, leaf in LINEARS:
        din = taps[leaf].shape[-1]
        x = taps[leaf].reshape(-1, din).astype(dt)
        hess = jnp.matmul(x.T, x, precision=HIGHEST,
                          preferred_element_type=dt) / x.shape[0]
        w_oi = p[parent][leaf]["w"].T.astype(dt)
        w1, scales, zeros = gptq(w_oi, hess, bits, group, percdamp)
        x_inst = taps[leaf][-instance:].reshape(-1, din).astype(dt)
        curv = (hess + percdamp * jnp.mean(jnp.diag(hess))
                * jnp.eye(din, dtype=dt)) * x_inst.shape[0]
        wq, hist, rounds = stage2(w_oi, w1, scales, zeros, x_inst, curv,
                                  bits, group, s2)
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        out[leaf] = Linear(f32(w_oi), f32(wq), f32(w1), f32(hess),
                           f32(scales), f32(zeros), f32(hist), rounds)
        q[parent][leaf] = dict(p[parent][leaf], w=wq.T.astype(jnp.float32))
    pq = dict(p, mixer=q["mixer"], mlp=q["mlp"])
    nxt = jax.lax.map(lambda h: block(mc, pq, h, act), hs)
    return nxt, out


def quant_chain(mc, seed: int, tokens: np.ndarray, quant: Dict,
                dtype_name: str = "f32"):
    """Yield (layer, {linear: Linear}) along the reference's own RPIQ
    chain over calibration ``tokens`` (batches, batch, S), the last batch
    the single instance; ``quant`` is the configuration's quant section.
    ``dtype_name="bf16"`` is the control."""
    key = W.root_key(seed)
    nb, batch, seq = tokens.shape
    emb, _ = jax.jit(W.embed_and_final_norm, static_argnums=0)(mc, key)
    with jax.default_matmul_precision("highest"):
        hs = jnp.take(emb, jnp.asarray(tokens.reshape(nb * batch, seq)),
                      axis=0)
        del emb
        for layer in range(mc.num_layers):
            p = jax.jit(W.layer_params, static_argnums=0)(
                mc, W.layer_key(key, layer))
            hs, out = _quant_layer_step(
                mc, p, hs, int(quant["bits"]), int(quant["group_size"]),
                float(quant["percdamp"]), Stage2.of(quant), batch,
                dtype_name)
            yield layer, out
