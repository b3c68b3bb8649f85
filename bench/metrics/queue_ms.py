"""queue_ms: mean admission delay (ms), `Response.queue_delay_ms`: from
`submit` to the start of the batch that served the request
(`serving.runtime.collect_batch`)."""
import numpy as np


def read(ctx):
    v = [f["queue_ms"] for f in ctx.answered()]
    return float(np.mean(v)) if v else None
