"""return_gap_ms_per_call.read: idle time of the target chip, on the
device's clock, from the end of a read's repair program to the start of
the next read's strip take: the wait for the answer, the host's work
between reads and the take's launch; mean over the reads in the traced
window."""
from yardstick import stages


def read(run):
    gaps = stages.read_gaps(run)
    return None if gaps is None else stages.mean_ms(gaps[1])
