"""Output tokens that reached the clients inside the window, per second of it.
Tokens of requests still in flight at the close count."""
from bench.harness import client as C


def read(run):
    return C.output_tokens(run.recs, run.window) / run.window.seconds
