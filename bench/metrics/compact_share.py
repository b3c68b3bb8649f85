"""compact_share: the compact (pass 2) kernels' share (%) of the device time
of all SNN kernels in the trace."""
# the Pallas kernels of `kernels/snn_query`, by the HLO names the
# device trace gives them (``%snn_count_stacked.1``, ``%snn_compact_stacked.1``)
KERNELS = r"snn_(count|compact|filter)"
COMPACT = r"snn_compact"


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s(KERNELS)
    if t <= 0:
        return None
    return 100.0 * ctx.trace.kernel_s(COMPACT) / t
