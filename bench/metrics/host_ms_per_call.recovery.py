"""host_ms_per_call.recovery: host time inside the program's repair entry
per recovery call, from entering it until it returns and before
block_until_ready (plan and spec rebuild, dispatch); mean over the calls."""
import numpy as np


def read(run):
    if run.kind != "node_recovery" or not run.dispatch_s:
        return None
    return float(np.mean(run.dispatch_s)) * 1e3
