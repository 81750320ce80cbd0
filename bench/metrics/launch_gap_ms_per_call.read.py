"""launch_gap_ms_per_call.read: idle time of the target chip, on the
device's clock, from the end of a read's strip take to the start of its
repair program: what the host's launch of the repair program costs the
chip; mean over the reads in the traced window."""
from yardstick import stages


def read(run):
    gaps = stages.read_gaps(run)
    return None if gaps is None else stages.mean_ms(gaps[0])
