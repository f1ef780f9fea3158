"""Time inside the radix index's eviction per admission prefill, in ms: the
engine's ``evict_ns`` counter over its ``prefill_calls`` in the window."""


def read(run):
    c = run.counters
    calls = c.get("prefill_calls", 0)
    if "evict_ns" not in c or not calls:
        return None
    return c["evict_ns"] / calls / 1e6
