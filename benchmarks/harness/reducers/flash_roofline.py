"""Roofline share of the flash-attention kernels: for every traced call,
the least time the chip could take for what THAT call needs (causal FLOPs
over the bf16 peak, or its bytes over the HBM peak, whichever is larger),
summed, over the kernels' summed device time, in %.  ``kernels`` lists each
kernel's name pattern and its kind (``fwd``, ``bwd_dq``, ``bwd_dkv``).  A
call's FLOPs and bytes are ``flash_call_flops`` / ``flash_call_bytes``
``(conf, batch, seq, kind)`` of ``harness/counts.py`` or, with ``counts``, of
the module of that name (``manifest.counts``): a kernel whose q/k and v head
sizes differ is counted by a file of its own."""

from benchmarks.harness import counts as default_counts, trace as T


def reduce(run, kernels: list, counts: str | None = None):
    if run.trace is None:
        return None
    module = run.manifest.counts(counts) if counts else default_counts
    chips = max(1, len(run.trace.devices))
    local_batch = max(1, run.notes["batch"] // chips)
    need = spent = 0.0
    bounds = set()
    for k in kernels:
        events = T.kernel_events(run.trace, k["pattern"])
        if not events:
            continue
        t, bound = default_counts.roofline_seconds(
            module.flash_call_flops(run.conf, local_batch, run.notes["seq"], k["kind"]),
            module.flash_call_bytes(run.conf, local_batch, run.notes["seq"], k["kind"]),
            run.peaks)
        bounds.add(bound)
        need += t * len(events) / chips
        spent += sum(e.seconds for e in events) / chips
    if spent <= 0.0:
        return None
    print(f"flash roofline: bound by {sorted(bounds)}; needs {need:.4f} s of "
          f"{spent:.4f} s kernel time in the traced window", flush=True)
    return 100.0 * need / spent
