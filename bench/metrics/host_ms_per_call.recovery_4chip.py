"""host_ms_per_call.recovery_4chip: host_ms_per_call.recovery of the cell
whose racks are chips, which moves recovery_4chip_MBps."""
from yardstick import spec

read = spec.load_reader("host_ms_per_call.recovery")
