"""Seconds a layer step spends outside the two executor stages (capture,
scatter, propagate): per job, the layer steps' wall less stage 1 and
stage 2 (all synchronised under ``quant.pipeline=serial``), over the
window's jobs and their steps."""


def read(ctx):
    jobs = ctx.records.get("jobs")
    if not jobs:
        return None
    rest = sum(sum(j["report"].layer_step_seconds)
               - j["report"].seconds_stage1 - j["report"].seconds_stage2
               for j in jobs)
    return rest / sum(len(j["report"].layer_step_seconds) for j in jobs)
