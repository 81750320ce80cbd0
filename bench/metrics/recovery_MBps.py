"""recovery_MBps: bytes of the failed node's blocks rebuilt in the window
over the window's seconds, in 10^6 bytes; every call counts, each ending
in block_until_ready."""


def read(run):
    if run.kind != "node_recovery" or not run.window_s:
        return None
    return run.rebuilt_bytes / run.window_s / 1e6
