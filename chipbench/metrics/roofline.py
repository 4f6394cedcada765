"""A kernel's share of its roofline: the least time the chip could take
for the kernel's work in the window (the larger of its operations over the
peak FLOP/s and its bytes over the peak bandwidth, ``chipbench/counts.py``),
over the summed device time of the kernel's events in the trace."""


def share(run, kernel):
    work = (run.get("kernel_work") or {}).get(kernel)
    t = run.get("trace")
    if work is None or t is None:
        return None
    seconds = t.kernel_s(kernel)
    if seconds <= 0:
        return None
    flops, nbytes = work
    p = run["peaks"]
    least = run["units"] * max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
