"""Model FLOP/s utilisation of the traced window: the operations the
model's work needs (``chipbench/counts.py``: per training step forward and
backward without recomputation, per prefill call the stack and the head over
the last token) times the steps or calls completed, over the window and the
chip's peak (``chipbench/peaks.json``)."""


def read(run):
    flops = run.get("unit_flops")
    if not flops or not run.get("units"):
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["trace"].devices
    return 100.0 * flops * run["units"] / (run["elapsed_s"] * peak)
