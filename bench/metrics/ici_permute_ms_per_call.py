"""ici_permute_ms_per_call: time per recovery call in which a chip is held
by the collective-permute start and done ops of the cross-rack hop, on
the chip that they hold longest; only where racks are chips.  This is
what the ICI costs the chips, not a transfer's time in flight, which
overlaps their own work.  The sending racks wait for their transfers;
the target, whose data has arrived by the time it needs it, barely."""
from yardstick import trace


def read(run):
    if run.trace is None or run.cell.chips < 2 or not run.latencies_s:
        return None
    secs = max((trace.op_seconds(run.trace, d, r"collective-permute")
                for d in run.trace["devices"]), default=0.0)
    if secs <= 0:
        return None
    return secs / len(run.latencies_s) * 1e3
