"""live_cells: the share (%) of the stacked kernels' grid cells whose
sorted window the kernel finds hit, over the window: the program's
counters `engine.DISPATCH_STATS.live_cells` / ``grid_cells`` (one grid of
live segments x query tiles x db blocks per pass run on the device).  The
other cells skip the MXU but still fetch their blocks."""


def read(ctx):
    grid = ctx.dispatch.get("grid_cells")
    if not grid:
        return None
    return 100.0 * ctx.dispatch["live_cells"] / grid
