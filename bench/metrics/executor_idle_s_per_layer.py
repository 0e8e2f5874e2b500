"""Idle device seconds per layer step of the traced quantize job whose
innermost host span is the plan executor's (stacking a group's stage
inputs, the stage calls and their syncs, the results;
``bench/phases.py``)."""
from bench import phases


def read(ctx):
    ph = phases.Phases.of(ctx)
    if ph is None or not ph.count(phases.STEP):
        return None
    return ph.idle_in(phases.EXECUTOR) / ph.count(phases.STEP)
