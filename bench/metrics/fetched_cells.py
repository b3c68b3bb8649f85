"""fetched_cells: the share (%) of the stacked kernels' grid cells that
fetch their row block, over the window: the program's counters
`engine.DISPATCH_STATS.fetched_cells` / ``grid_cells``.  A cell fetches
when it lies in its (segment, query tile)'s live block range; the pruned
cells outside it skip the fetch as well as the matmul.  A program without
the ``fetched_cells`` counter, whose every cell fetches, gives nothing."""


def read(ctx):
    grid = ctx.dispatch.get("grid_cells")
    fetched = ctx.dispatch.get("fetched_cells")
    if not grid or fetched is None:
        return None
    return 100.0 * fetched / grid
