"""repair_hbm_roofline.recovery: the least time the window's repairs could
take on the target chip, bound by HBM bytes (each helper's block read
once, the rebuilt block written once, at the chip's peak bandwidth),
over the chip's busy time in the window; in percent."""
from yardstick import trace


def read(run):
    if run.kind != "node_recovery" or run.trace is None:
        return None
    busy = trace.busy_s(run.trace, run.target_device)
    if busy <= 0:
        return None
    least = run.least_hbm_bytes / run.peaks["hbm_bytes_per_s"]
    return least / busy * 100
