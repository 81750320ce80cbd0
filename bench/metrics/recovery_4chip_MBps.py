"""recovery_4chip_MBps: recovery_MBps of the cell whose racks are chips.
It is a metric of its own because its runs spread far less than those of
a one-chip cell, and so hold a tighter bound."""
from yardstick import spec

read = spec.load_reader("recovery_MBps")
