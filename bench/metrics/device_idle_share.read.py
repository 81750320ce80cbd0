"""device_idle_share.read: share of the traced window in which no op ran
on the target chip, in a degraded-read cell; in percent."""
from yardstick import trace


def read(run):
    if run.kind != "degraded_read" or run.trace is None or not trace.has_ops(
            run.trace, run.target_device):
        return None
    return trace.idle_share(run.trace, run.target_device) * 100
