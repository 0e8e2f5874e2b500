"""Model operations (what the mathematics needs, not what a program runs)
of the OPT-proxy block and of an RPIQ quantize job, for ``quant_mfu``.
``dims`` is a ``bench.weights.Dims``. A multiply-add counts 2."""
from __future__ import annotations


def linear_params(dims) -> int:
    """Weights of the six linears of one layer."""
    d, f = dims.d_model, dims.d_ff
    return 4 * d * d + 2 * d * f


def causal_pairs(start: int, c: int) -> int:
    """(query, key) pairs of positions start..start+c-1 under causality."""
    return c * start + c * (c + 1) // 2


def layer_forward(dims, n_seq: int, seq: int) -> float:
    """One layer over ``n_seq`` sequences of ``seq`` positions."""
    d = dims.d_model
    return float(n_seq * (2 * seq * linear_params(dims)
                          + 4 * d * causal_pairs(0, seq)))


def quant_job(dims, calib_shape, linears, instance_rows: int) -> float:
    """What an RPIQ quantize job needs: per layer a capture and a
    propagate forward over the calibration set; per distinct linear input
    a Hessian (q, k, v share theirs); per linear the damped inverse and
    its Cholesky factor (about 2 in^3), the GPTQ sweep (out in^2) and,
    where stage 2 ran, its start (4 n in out) and each round run
    (4 n in out + 2 128 in out) on the ``instance_rows`` of the single
    instance. ``linears`` are the report's records."""
    nb, bs, seq = calib_shape
    n_tok = nb * bs * seq
    d, f = dims.d_model, dims.d_ff
    total = dims.num_layers * 2 * layer_forward(dims, nb * bs, seq)
    total += dims.num_layers * 2.0 * n_tok * (3 * d * d + f * f)
    for rec in linears:
        out, inp = rec.shape
        total += 2.0 * inp ** 3 + float(out) * inp * inp
        if rec.mode == "rpiq":
            pass_ = 4.0 * instance_rows * inp * out
            total += pass_ + rec.iters * (pass_ + 2.0 * 128 * inp * out)
    return total
