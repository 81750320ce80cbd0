"""read_p50_ms: median latency of all degraded reads in the window, from
taking the strips to block_until_ready of the rebuilt strip."""
import numpy as np


def read(run):
    if run.kind != "degraded_read" or not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 50)) * 1e3
