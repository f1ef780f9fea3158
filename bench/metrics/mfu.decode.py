"""The whole step's share of the chip's peak in a decode-heavy cell: least
time of the traced window's model work at the published peaks over the window,
in %. Counts real tokens only, so padding and idle time both lower it."""
from bench.harness import work


def read(run):
    return work.mfu(run)
