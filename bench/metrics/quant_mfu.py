"""The traced job's required model operations (bench/flops.quant_job:
capture and propagate forwards, Hessians, factors, GPTQ sweeps, the RPIQ
rounds run) over the traced window times the chip's bf16 peak (%)."""
from bench import flops, readers


def read(ctx):
    if not ctx.peaks:                    # a device without a peaks entry
        return None
    tr, k = ctx.trace, ctx.records.get("traced_job")
    if tr is None or k is None:
        return None
    rep = ctx.records["jobs"][k]["report"]
    nb, bs, seq = ctx.records["calib_shape"]
    work = flops.quant_job(ctx.records["dims"], (nb, bs, seq), rep.linears,
                           bs * seq)
    return readers.share(ctx, "quant_mfu",
                         work / ctx.peaks["bf16_flops_per_s"], tr.window_s)
