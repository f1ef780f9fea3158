"""95th percentile of the time to first token, from the send (the due time)
to the first token at the client, over every request whose first token came
inside the window; milliseconds."""
from bench.harness import client as C


def read(run):
    v = C.p95(C.ttfts(run.recs, run.window))
    return None if v is None else 1000.0 * v
