"""snn_roofline: the SNN kernels' share of their roofline (%).

The least device time the window's answered requests need (the sorted
window's pairs at the bf16 peak, or the rows they read at the HBM peak,
whichever is longer; `snnbench.roofline`), over the summed device time of
the SNN Pallas kernels in the trace.
"""
# the Pallas kernels of `kernels/snn_query`, by the HLO names the
# device trace gives them (``%snn_count_stacked.1``, ``%snn_compact_stacked.1``)
KERNELS = r"snn_(count|compact|filter)"


def read(ctx):
    if ctx.trace is None or ctx.work is None:
        return None
    t = ctx.trace.kernel_s(KERNELS)
    if t <= 0:
        return None
    return 100.0 * ctx.work["seconds"] / t
