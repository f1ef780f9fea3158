"""Share of slot-steps that served a live request, over the window's decode
steps (the engine's ``active_slot_steps`` / (``decode_steps`` x batch)), in %."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    if not steps:
        return None
    B = run.cell.traffic["engine"]["batch_size"]
    return 100.0 * run.counters["active_slot_steps"] / (steps * B)
