"""Model work and kernel events inside the traced window, for the readers."""
from __future__ import annotations

from bench.harness import flops


def _decode_work(run, m) -> flops.Work:
    """Every token of the traced window after a request's first: one decode
    step at its context length."""
    tw = run.trace_window
    total = flops.Work()
    for r in run.recs:
        p = len(r.prompt)
        for j, t in enumerate(r.times[1:], 1):
            if tw.holds(t):
                total = total + flops.token_work(m, p + j)
    return total


def model_work(run) -> flops.Work:
    """The work the served tokens of the traced window needed: for a request
    whose first token came in the window, its prompt (less the prefix it
    reused from cache); for every later token, one decode step at its
    context length. Counted from the client's records, so padded rows and
    recomputation never count. Fits an engine that computes a prompt in one
    admission call, shortly before its first token."""
    tw = run.trace_window
    m = run.cell.model.work(run.cell.config)
    total = _decode_work(run, m)
    for r in run.recs:
        if r.times and tw.holds(r.times[0]):
            total = total + flops.prefill_work(m, len(r.prompt), r.prefix_reused)
    return total


def chunked_work(run) -> flops.Work:
    """The same for an engine that computes prompts in chunks over many
    steps, so that a long prompt's work spreads past the traced window. The
    prompt tokens computed inside it are the engines' ``chunk_prefill_rows``
    counter over the traced window. Each counts as attending to a whole
    prompt: the mean prompt length of the requests that waited for their
    first token inside the traced window, weighted by how long each waited
    there. That is an upper bound, near for a turn whose history was cached
    and twice the mean for a prompt computed whole. A prompt's head counts
    with its first token, decode steps as in ``model_work``. None without
    the counter."""
    tw = run.trace_window
    rows = run.trace_counters.get("chunk_prefill_rows")
    if rows is None:
        return None
    m = run.cell.model.work(run.cell.config)
    total = _decode_work(run, m)
    weight = ctx = 0.0
    for r in run.recs:
        first = r.times[0] if r.times else run.window.end
        inside = min(first, tw.end) - max(r.due, tw.start)
        if inside > 0:
            weight += inside
            ctx += inside * len(r.prompt)
        if r.times and tw.holds(first):
            total = total + flops.Work(bf16_ops=m.head_ops)
    if rows and weight:
        total = total + flops.prompt_rows_work(m, rows, ctx / weight)
    return total


def mfu(run, work=model_work):
    """Least time of the window's model work (``work(run)``) at one chip's
    peaks, over the traced window and the cell's chips, in percent; None
    without a trace or without work."""
    if run.trace is None or run.peaks is None:
        return None
    w = work(run)
    if w is None or (w.int8_ops == 0 and w.bf16_ops == 0):
        return None
    return 100.0 * w.least_seconds(run.peaks) / (run.trace.window_s * run.cell.chips)


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
