"""launches_per_row: device launches per query row answered in the window,
from the program's counter `engine.DISPATCH_STATS.kernel_launches`."""


def read(ctx):
    rows = sum(f["rows"] for f in ctx.answered())
    if not rows:
        return None
    return ctx.dispatch["kernel_launches"] / rows
