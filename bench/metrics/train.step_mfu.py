"""The whole step's share of the chip's roofline.

Least time per step = max(FLOPs / (chips x peak FLOP/s), bytes / (chips x
peak bytes/s)), with the FLOPs and bytes the configured algorithm needs for
one step of the whole job (``bench/work/<config>.py``, from the
configuration's shapes and optimizer, whatever implements them), over the
measured time per step: the traced window's length over its steps. Dense
AdamW over the hashed tables makes memory bandwidth the bound, not FLOPs.
"""


def reduce(run):
    if not run.devices or run.steps_per_execution < 1:
        return None
    steps = run.mean_device("executions") * run.steps_per_execution
    if steps <= 0:
        return None
    flops, nbytes = run.cell.work().least_work(run.cell.config)
    peaks = run.cell.peaks(run.device_kind)
    chips = run.cell.chips
    least = max(flops / (chips * peaks["bf16_flops_per_s"]),
                nbytes / (chips * peaks["hbm_bytes_per_s"]))
    return 100.0 * least / (run.mean_device("window_s") / steps)
