"""read_p95_ms: 95th percentile latency of all degraded reads in the window."""
import numpy as np


def read(run):
    if run.kind != "degraded_read" or not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 95)) * 1e3
