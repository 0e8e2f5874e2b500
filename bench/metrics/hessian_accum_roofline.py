"""Roofline share (%) of the Pallas ``hessian_accum`` calls in the traced
job, H = X^T X. Each call is counted from the shapes its trace event
names: the result (d, d) and the operand x (n, d)
(``bench/opcount/hessian_accum.py``)."""
from bench import readers


def read(ctx):
    oc = ctx.opcount("hessian_accum")

    def work(shapes):
        n, d = shapes[1]
        return oc.count(n, d)
    return readers.kernel_roofline(ctx, "hessian_accum", work)
