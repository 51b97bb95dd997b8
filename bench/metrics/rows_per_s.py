"""rows_per_s: verdicts completed in the window over the window's seconds."""


def read(ctx):
    run = ctx.run
    if "latency_s" in run:
        return None
    return run["dec"].size / run["elapsed_s"]
