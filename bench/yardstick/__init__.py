"""The benchmark's yardstick: cell lookup, traffic, data and reference,
the window and the check, the trace reduction.  It imports nothing of
the program except the system under test (``bench/yardstick/runner.py``'s
``Program``)."""
