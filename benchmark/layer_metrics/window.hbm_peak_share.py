"""The whole window's share of the chip's HBM peak: the bytes of
scan_hbm_roofline over all the seconds of the window, idle ones too.  A
change that takes a program off the device's path leaves that roofline
silent; this one still bounds what it can claim."""
from metrics import scanned_bytes


def read(run):
    needed = scanned_bytes(run) if run["peaks"] else 0
    if not needed:
        return None
    return 100.0 * needed / run["peaks"]["hbm_bytes_per_s"] / run["window_s"]
