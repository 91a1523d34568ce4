"""The device's idle share over the traced window of a cell with
interruptions, read as `device_idle_frac` is (device trace); there it
moves goodput, recoveries' idle time included."""
from chipbench import spec

read = spec.reader("device_idle_frac")
