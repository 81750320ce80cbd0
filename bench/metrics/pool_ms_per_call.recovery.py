"""pool_ms_per_call.recovery: device time per recovery call on the target
chip, in the traced window, under the program's ``inner`` and ``cross``
scopes together: a rack's pool assembled from its nodes' units, and the
units shipped to the target rack (a local take where racks share a
chip)."""
from yardstick import stages


def read(run):
    return stages.stage_ms_per_call(run, "inner", "cross")
