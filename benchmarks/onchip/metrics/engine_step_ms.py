"""Mean host time of one engine step over the window
(``VisionStats.wall_s`` over ``steps``): batch stacking, the copy to the
device, the executable and the synchronous copy back."""


def read(run):
    e = run.engine
    return 1e3 * e["wall_s"] / e["steps"] if e["steps"] else None
