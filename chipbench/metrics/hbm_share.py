"""Share of the chip's HBM bandwidth that the decode steps' necessary reads
take: bytes a step must read (every weight once and the keys and values of
every cached token it attends over, ``chipbench/counts.py``) times the
steps, over the traced window and the peak bandwidth."""


def read(run):
    nbytes = run.get("unit_bytes")
    if not nbytes or not run.get("units"):
        return None
    peak = run["peaks"]["hbm_bytes_per_s"] * run["trace"].devices
    return 100.0 * nbytes * run["units"] / (run["elapsed_s"] * peak)
