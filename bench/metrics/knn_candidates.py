"""knn_candidates: result pairs of the kNN front-end's final count ->
compact pass per neighbor asked for, over the window: the program's
counters `engine.DISPATCH_STATS.knn_candidates` / ``knn_wanted`` (sum of
each query's k).  1 means the final balls held exactly k points; the
excess is what the float64 refinement sorts through and discards."""


def read(ctx):
    wanted = ctx.dispatch.get("knn_wanted")
    if not wanted:
        return None
    return ctx.dispatch["knn_candidates"] / wanted
