"""The whole step's share of the chips' peak in the sessions cell: least time
of the traced window's model work at the published peaks, over the window and
the cell's chips, in %. The engine computes prompts in chunks, so the prompt
tokens are those the engines computed inside the traced window (their
``chunk_prefill_rows`` counter), and every decoded token counts at its context
(``work.chunked_work``). Counts real tokens only, so padding, recomputed
prefixes and idle time all lower it."""
from bench.harness import work


def read(run):
    return work.mfu(run, work.chunked_work)
