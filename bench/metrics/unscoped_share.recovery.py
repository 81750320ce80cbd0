"""unscoped_share.recovery: share of the target chip's busy time in the
traced window under no stage scope of the repair program, in percent;
what the stage metrics leave out."""
from yardstick import stages, trace


def read(run):
    got = stages.recovery_stages(run)
    if got is None:
        return None
    busy = trace.busy_s(run.trace, run.target_device)
    if busy <= 0:
        return None
    return (busy - sum(got.values())) / busy * 100
