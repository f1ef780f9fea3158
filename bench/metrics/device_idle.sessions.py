"""Share of the traced window in which no operation ran on a chip, in %,
averaged over the cell's chips: 100 x (1 - mean over the chips of the union
of each chip's op intervals / window)."""


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
