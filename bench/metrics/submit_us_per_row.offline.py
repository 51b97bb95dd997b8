"""submit_us_per_row.offline: host time of QWYCServer.submit calls that
did not flush (admission and queueing), per row."""


def read(ctx):
    run = ctx.run
    if not run.get("submit_rows"):
        return None
    return run["submit_s"] / run["submit_rows"] * 1e6
