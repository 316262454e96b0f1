"""Share of the HBM roofline that the traced window's device time reached:
the least seconds the chip needs for `metrics.scanned_bytes` at its peak
HBM rate, over the seconds in which any operation ran on the device.
These queries are bound by memory, not by operations."""
from metrics import scanned_bytes


def read(run):
    if not run["trace"] or not run["peaks"] or not run["trace"]["busy_s"]:
        return None
    floor_s = scanned_bytes(run) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / run["trace"]["busy_s"] if floor_s else None
