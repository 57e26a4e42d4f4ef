"""Share of the conv stages' roofline: the least time the chip needs for
every conv stage's work in the window (the larger of operations over
peak and HBM bytes over bandwidth, per stage, from its padded and
strided shapes at the lanes issued) over the device time of the conv
kernels' events, whichever kernel implements a stage (``conv_window.s<i>``
or ``fused_cwp.s<i>``, matched on the event's instruction name)."""
from trace_reduce import op_time_s, short

KERNELS = ("conv_window", "fused_cwp")


def is_conv_kernel(op) -> bool:
    return short(op.name).lstrip("%").startswith(KERNELS)


def read(run):
    if run.trace is None:
        return None
    kernel_s = op_time_s(run.trace, is_conv_kernel)
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
