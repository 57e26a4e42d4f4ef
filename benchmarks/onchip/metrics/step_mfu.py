"""The whole step's share of the chip's peak: the model's operations for
the images answered in the window, over the window's length and the
peak the configuration is held to."""


def read(run):
    flops = run.peak["flops_per_s"][run.cell.config["peak"]]
    return 100.0 * run.done_in_window * run.flops_per_image / (
        run.seconds * flops)
