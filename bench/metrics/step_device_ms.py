"""Device time of one engine step program (decode, admission or packed chunk
step, as the trace names the jitted functions) averaged over its launches in
the traced window; milliseconds. The first and last launch on the line are
left out: the trace's edges may cut them."""

STEPS = ("decode_step", "admit_step", "chunked_step")


def read(run):
    if run.trace is None:
        return None
    ev = []
    for chip in run.trace.chips:
        mods = sorted(chip.modules, key=lambda e: e.start_ns)[1:-1]
        ev += [e for e in mods if any(s in e.name for s in STEPS)]
    if not ev:
        return None
    return sum(e.dur_ns for e in ev) / len(ev) / 1e6
