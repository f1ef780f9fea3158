"""Share of the requests the server placed in the window that the router sent
to a replica holding a cached prefix of the prompt, in %: the router's
``affinity_hits`` over the server's ``routed`` count, both over the window."""


def read(run):
    s = run.server
    if not s.get("routed") or "affinity_hits" not in s:
        return None
    return 100.0 * s["affinity_hits"] / s["routed"]
