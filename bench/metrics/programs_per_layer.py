"""Device programs (``XLA Modules`` events) that start inside a layer
step of the traced quantize job, per step (``bench/phases.py``)."""
from bench import phases


def read(ctx):
    ph = phases.Phases.of(ctx)
    if ph is None or not ph.count(phases.STEP):
        return None
    return ph.programs_in(phases.STEP) / ph.count(phases.STEP)
