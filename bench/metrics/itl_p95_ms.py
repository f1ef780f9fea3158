"""95th percentile over every gap between consecutive tokens of every request,
at the client, whose later token came inside the window; milliseconds."""
from bench.harness import client as C


def read(run):
    v = C.p95(C.gaps(run.recs, run.window))
    return None if v is None else 1000.0 * v
