"""Roofline share of the paged-attention kernel in the decode step: the
bytes of the LIVE keys and values a call must read (every active lane's
length, one layer) over the HBM peak — or its FLOPs over the bf16 peak,
whichever takes longer — against the call's device time, both as means over
the traced decode steps, in %.  A kernel that attends the padded lane length
shows as a low share."""

from benchmarks.harness import counts, stats, trace as T


def reduce(run, pattern: str, module: str | None = None):
    live = run.notes.get("kv_tokens")
    if run.trace is None or not live:
        return None
    events = T.kernel_events(run.trace, pattern, module)
    if not events:
        return None
    tokens = stats.mean(live)
    need, bound = counts.roofline_seconds(
        counts.paged_decode_call_flops(run.conf, tokens),
        counts.paged_decode_call_bytes(run.conf, tokens), run.peaks)
    spent = stats.mean([e.seconds for e in events])
    print(f"paged roofline: bound by {bound}; a call needs {need * 1e6:.1f} us "
          f"for {tokens:.0f} live tokens and takes {spent * 1e6:.1f} us "
          f"(mean of {len(events)} calls)", flush=True)
    return 100.0 * need / spent
