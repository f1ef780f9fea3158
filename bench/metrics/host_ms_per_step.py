"""The engine's own host time per decode step, in ms: (time inside
``ServeEngine.step`` less time blocked on sampled tokens) over the window's
decode steps, from the engine's ``step_ns``, ``sync_ns`` and ``decode_steps``
counters. Admissions, eviction and emission count; device waits do not."""


def read(run):
    c = run.counters
    steps = c.get("decode_steps", 0)
    if "step_ns" not in c or "sync_ns" not in c or not steps:
        return None
    return (c["step_ns"] - c["sync_ns"]) / steps / 1e6
