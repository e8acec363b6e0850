"""The share of its roofline of a family of kernels that together do one
piece of work (an attention core's forward and its two backward
kernels), as ONE share over all of them: numerator and denominator hold
the forward and the backward alike, so the share reads the same work
whatever implements it, and cannot drop when a scan becomes a kernel."""


def share_by_counted_calls(run: dict, *, contains: list, counts: str):
    """In percent: the least time the chip could take for the kernels'
    calls of the traced slice over the summed device time of the
    operations whose path holds every entry of ``contains``. The family's
    function ``counts(cfg, wl)`` gives kernel name -> (operations, bytes)
    of ONE call, closed forms that never look at the trace; the least
    time of a call is the larger of operations / peak FLOP/s and bytes /
    peak bytes/s; a kernel's calls are the executions, over the slice, of
    the operations under the path whose own name holds the kernel's (a
    recomputed call counts as a call; a neighbour's fusion that carries
    the path of one of a kernel's instructions adds its time to the
    denominator and no call). A count that is too high reads over 100%.
    Without a trace, without paths, with no call of any of the kernels, a
    family without the function or a device without a peak it returns
    nothing."""
    t, fn = run["trace"], getattr(run["family"], counts, None)
    peak = run["peaks"].get(run["device"]["kind"])
    if not t or fn is None or peak is None:
        return None
    under = [(name, s, n) for name, path, s, n in t["ops"]
             if path and all(c in path for c in contains)]
    seconds = sum(s for _, s, _ in under)
    least = sum(
        n * max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
        for kernel, (flops, nbytes) in fn(run["cfg"], run["wl"]).items()
        for name, _, n in under if kernel in name)
    if not seconds or not least:
        return None
    return 100.0 * least / seconds
