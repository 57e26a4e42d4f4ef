"""Share of the fused conv kernel's roofline: the least time the chip
needs for the conv stages' work in the window (the larger of operations
over peak and minimal HBM bytes over bandwidth, per stage, from the
shapes) over the device time of the kernel's events in the trace."""
from trace_reduce import op_time_s

KERNEL = "fused_cwp"


def read(run):
    if run.trace is None:
        return None
    kernel_s = op_time_s(run.trace, lambda op: KERNEL in op.name
                         or KERNEL in op.detail)
    steps = run.engine["steps"]
    lanes = run.engine["lane_steps"] + run.engine["pad_lanes"]
    if kernel_s <= 0 or steps == 0:
        return None
    fam, cfg = run.cell.family, run.cell.config
    flops = run.peak["flops_per_s"][cfg["peak"]]
    per_step = lanes / steps
    least = steps * sum(
        max(fam.stage_ops(st) * per_step / flops,
            fam.stage_bytes(st, per_step) / run.peak["hbm_bytes_per_s"])
        for st in run.stages)
    return 100.0 * least / kernel_s
