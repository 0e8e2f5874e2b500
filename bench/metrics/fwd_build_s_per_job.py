"""Seconds the traced quantize job spent building its capture and
propagate forwards: the sum of its ``quant.fwd_build`` spans, each a
``ForwardCache`` miss (the jit's first call: trace, lower, and compile
or load from the persistent cache). Their count is logged."""
from bench import phases


def read(ctx):
    ph = phases.Phases.of(ctx)
    if ph is None:
        return None
    ctx.log(f"fwd_build_s_per_job: {ph.count(phases.FWD_BUILD)} builds")
    return ph.seconds_in(phases.FWD_BUILD)
