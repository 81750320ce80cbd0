"""relayer_encode_ms_per_call.recovery: device time per recovery call on
the target chip, in the traced window, under the program's
``relayer_encode`` scope; None where the code has no relayers, so that
no instruction of the program carries the scope."""
from yardstick import stages


def read(run):
    if "relayer_encode" not in (stages.recovery_stages(run) or {}):
        return None
    return stages.stage_ms_per_call(run, "relayer_encode")
