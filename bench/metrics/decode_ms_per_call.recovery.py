"""decode_ms_per_call.recovery: device time per recovery call on the
target chip, in the traced window, under the program's ``decode`` scope:
the target's gather of its units and the decode product."""
from yardstick import stages


def read(run):
    return stages.stage_ms_per_call(run, "decode")
