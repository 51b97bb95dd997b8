"""setup_s: seconds from process start to the opening of the measured
window (loading, compiling or reading compiled programs, warming up)."""


def read(ctx):
    return ctx.setup_s
