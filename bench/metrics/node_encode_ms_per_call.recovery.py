"""node_encode_ms_per_call.recovery: device time per recovery call on the
target chip, in the traced window, in which the innermost op running
carries the program's ``node_encode`` scope: NodeEncode, with the split
of each resident stripe into the rows it reads."""
from yardstick import stages


def read(run):
    return stages.stage_ms_per_call(run, "node_encode")
