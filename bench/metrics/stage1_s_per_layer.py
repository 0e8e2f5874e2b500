"""Stage-1 (damp, factor, GPTQ sweep) seconds per layer step, from the
report of each job in the window."""


def read(ctx):
    jobs = ctx.records.get("jobs")
    if not jobs:
        return None
    return (sum(j["report"].seconds_stage1 for j in jobs)
            / sum(len(j["report"].layer_step_seconds) for j in jobs))
