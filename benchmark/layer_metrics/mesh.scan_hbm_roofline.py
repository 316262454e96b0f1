"""Share of the HBM roofline of ALL the mesh's chips that the traced
span's device time reached: `metrics.scanned_bytes` (the resident bytes
of the touched columns, each shard on its own chip, once a query) over
the summed peak rate of the chips whose planes the trace holds, over the
seconds in which an operation ran on a chip (`busy_s`, which the
reduction averages over those planes).  `scan_hbm_roofline` divides by
one chip's rate and would read that many times too high here."""
from metrics import scanned_bytes


def read(run):
    trace = run["trace"]
    if not trace or not run["peaks"] or not trace["busy_s"] \
            or not trace.get("device_planes"):
        return None
    rate = trace["device_planes"] * run["peaks"]["hbm_bytes_per_s"]
    floor_s = scanned_bytes(run) / rate
    return 100.0 * floor_s / trace["busy_s"] if floor_s else None
