"""Share of the window's admitted prompt tokens that the engines served from
their prefix cache, in %: ``prefix_tokens_reused`` / ``prompt_tokens``, each
summed over the replicas' engine counters over the window."""


def read(run):
    c = run.counters
    if not c.get("prompt_tokens"):
        return None
    return 100.0 * c.get("prefix_tokens_reused", 0) / c["prompt_tokens"]
