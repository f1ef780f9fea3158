"""Model work and kernel events inside the traced window, for the readers."""
from __future__ import annotations

from bench.harness import flops


def model_work(run) -> flops.Work:
    """The work the served tokens of the traced window needed: for a request
    whose first token came in the window, its prompt (less the prefix it
    reused from cache); for every later token, one decode step at its
    context length. Counted from the client's records, so padded rows and
    recomputation never count."""
    c, tw = run.cell.config, run.trace_window
    total = flops.Work()
    for r in run.recs:
        p = len(r.prompt)
        for j, t in enumerate(r.times):
            if tw.holds(t):
                total = total + (flops.prefill_work(c, p, r.prefix_reused) if j == 0
                                 else flops.token_work(c, p + j))
    return total


def mfu(run):
    """Least time of the window's model work at the chip's peaks, over the
    traced window, in percent; None without a trace."""
    if run.trace is None or run.peaks is None:
        return None
    w = model_work(run)
    if w.int8_ops == 0 and w.bf16_ops == 0:
        return None
    return 100.0 * w.least_seconds(run.peaks) / run.trace.window_s


def kernel_events(run, wrapper: str):
    """Device op events of a Pallas kernel: the trace names each op by its HLO
    instruction, and a kernel's custom call is named after the jitted wrapper
    that launches it (``%_paged_decode_attention.8 = ... custom-call(...)``)."""
    if run.trace is None:
        return []
    head = f"%{wrapper}."
    return [e for e in run.trace.ops() if e.name.startswith(head) and " custom-call(" in e.name]


def decode_contexts(run):
    """Context length of every decode step a served token of the traced window
    came from."""
    tw = run.trace_window
    return [len(r.prompt) + j for r in run.recs
            for j, t in enumerate(r.times) if j > 0 and tw.holds(t)]
