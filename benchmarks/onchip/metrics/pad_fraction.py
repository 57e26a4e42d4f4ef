"""Share of the issued batch lanes that were padding, over the window
(``VisionStats.pad_lanes`` against ``lane_steps``)."""


def read(run):
    issued = run.engine["lane_steps"] + run.engine["pad_lanes"]
    return 100.0 * run.engine["pad_lanes"] / issued if issued else None
