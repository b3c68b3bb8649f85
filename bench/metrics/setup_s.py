"""setup_s: process start to the first timed request (s): data, traffic
plan and radii, index build, warm-up and compiles."""


def read(ctx):
    return ctx.setup_s
