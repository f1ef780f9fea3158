"""Share of its roofline that the paged decode attention kernel reached in the
traced window, in %: the least time to read every served token's cached
context once per paged attention layer (int8 K/V pages and their scales; the
layers' shape from the model module's ``attention``), at the published
bandwidth, over the summed device time of the kernel's calls. The context
lengths are the served tokens', from the clients' records."""
from bench.harness import flops, work

KERNEL = "_paged_decode_attention"


def read(run):
    ev = work.kernel_events(run, KERNEL)
    ctx = work.decode_contexts(run)
    if not ev or not ctx:
        return None
    a = run.cell.model.attention(run.cell.config)
    if a is None:
        return None
    need = flops.paged_decode(ctx, a["n_heads"], a["n_kv_heads"], a["head_dim"])
    least = need.scale(a["layers"]).least_seconds(run.peaks)
    return 100.0 * least / (sum(e.dur_ns for e in ev) / 1e9)
