"""p50_ms: the median request latency over the window (ms).

Open loop: each request is timed from when it was due to when its response
was seen, so waiting behind a stall counts.  Every request due in the window
counts; one that failed counts with the time it was waited for.
"""
import numpy as np


def read(ctx):
    lat = [(r.done - r.due) * 1e3 for r in ctx.records]
    return float(np.percentile(lat, 50)) if lat else None
