"""write_ms_per_call.recovery: device time per recovery call on the
target chip, in the traced window, under the program's ``write`` scope:
the whole zero-filled output written around the rebuilt block."""
from yardstick import stages


def read(run):
    return stages.stage_ms_per_call(run, "write")
