"""order_cells: how many times fewer grid cells the served batches ran
than they would have tiled in the order their rows arrived, over the
window: the program's counters `engine.DISPATCH_STATS.arrival_cells` /
``live_cells`` (x).  1 means no batch was reordered, or the order saved
nothing; a program without the ``arrival_cells`` counter gives nothing."""


def read(ctx):
    arrival = ctx.dispatch.get("arrival_cells")
    live = ctx.dispatch.get("live_cells")
    if not arrival or not live:
        return None
    return arrival / live
