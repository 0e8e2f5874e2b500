"""Idle device seconds per layer step of the traced quantize job whose
innermost host span is the walk's own (the step itself, slicing its
params, capture, plan, scatter, propagate; ``bench/phases.py``). The
whole split of the job's idle time by innermost span is logged."""
from bench import phases


def read(ctx):
    ph = phases.Phases.of(ctx)
    if ph is None or not ph.count(phases.STEP):
        return None
    idle = sum(ph.idle.values())
    ctx.log("idle by innermost span: " + ", ".join(
        f"{k} {v:.6g} s ({100 * v / idle:.3g} %)"
        for k, v in ph.idle_gaps(top=len(ph.idle))))
    return ph.idle_in(phases.WALK) / ph.count(phases.STEP)
