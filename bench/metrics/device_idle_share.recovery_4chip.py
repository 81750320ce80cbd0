"""device_idle_share.recovery_4chip: device_idle_share.recovery of the
cell whose racks are chips, on the target rack's chip."""
from yardstick import spec

read = spec.load_reader("device_idle_share.recovery")
