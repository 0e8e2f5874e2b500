"""Roofline share (%) of the Pallas ``gptq_block`` sweeps in the traced
job. Each call is counted from the shapes its trace event names: the
result's first array is the group's weights (lanes, out, in), and each
lane is one linear's sweep (``bench/opcount/gptq_block.py``). The linears
whose U is over the kernel's VMEM budget take the XLA sweep and are not
in it."""
from bench import readers


def read(ctx):
    group = ctx.config["quant"]["group_size"]
    oc = ctx.opcount("gptq_block")

    def work(shapes):
        lanes, out, inp = shapes[0]
        f, b = oc.count(out, inp, group)
        return lanes * f, lanes * b
    return readers.kernel_roofline(ctx, "gptq_block", work)
