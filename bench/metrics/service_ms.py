"""service_ms: mean service time (ms), `Response.service_ms`: from the
start of the request's batch to its response (`TenantRuntime.run_batch`)."""
import numpy as np


def read(ctx):
    v = [f["service_ms"] for f in ctx.answered()]
    return float(np.mean(v)) if v else None
