"""Share of the device's busy time in the window spent outside the conv
kernels (``conv_window.s<i>``, ``fused_cwp.s<i>``): padding, residual
adds, ReLUs, pools, layout transposes and copies around them."""
from trace_reduce import busy_s, op_time_s, short

KERNELS = ("conv_window", "fused_cwp")


def read(run):
    if run.trace is None:
        return None
    busy = busy_s(run.trace)
    conv = op_time_s(run.trace,
                     lambda op: short(op.name).lstrip("%").startswith(
                         KERNELS))
    if busy <= 0 or conv <= 0:
        return None
    return 100.0 * (busy - conv) / busy
