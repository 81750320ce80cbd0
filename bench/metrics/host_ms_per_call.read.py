"""host_ms_per_call.read: host time inside the program's repair entry per
degraded read, before block_until_ready; mean over the reads."""
import numpy as np


def read(run):
    if run.kind != "degraded_read" or not run.dispatch_s:
        return None
    return float(np.mean(run.dispatch_s)) * 1e3
