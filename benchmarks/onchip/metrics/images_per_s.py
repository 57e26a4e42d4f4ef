"""Images answered inside the measured window, over the window's
length."""


def read(run):
    return run.done_in_window / run.seconds
