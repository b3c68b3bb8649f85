"""rows_per_s: query rows answered inside the window, per second of it.

A request answered after the window closed does not count; one answered
with an error does not count.
"""


def read(ctx):
    t_end = ctx.t0 + ctx.seconds
    rows = sum(ctx.facts[r.i]["rows"] for r in ctx.records
               if r.error is None and r.done <= t_end)
    return rows / ctx.seconds
