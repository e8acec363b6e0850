"""A kernel's share of its roofline with the kernel's calls counted in
the trace, not stated by the family."""


def share_by_counted_calls(run: dict, *, contains: list, kernel: str,
                           counts: str):
    """In percent: the least time the chip could take for the kernel's
    calls of the traced slice over the summed device time of the
    operations whose path holds every entry of ``contains``. The least
    time of ONE call is the larger of operations / peak FLOP/s and bytes
    / peak bytes/s, both from the family's function ``counts(cfg, wl)``,
    closed forms that never look at the trace; the calls are the
    executions, over the slice, of those operations whose own name holds
    ``kernel`` (the kernel's operation is named after it; a neighbour's
    fusion that carries the path of one of the kernel's instructions
    adds its time to the denominator and no call). So a step that runs
    the kernel again under ``jax.checkpoint`` counts it again, and one
    that saves its output does not: the share stays the kernel's own. A
    count that is too high still reads over 100%. Without a trace,
    without paths, with no such operation, a family without the function
    or a device without a peak it returns nothing."""
    t, fn = run["trace"], getattr(run["family"], counts, None)
    peak = run["peaks"].get(run["device"]["kind"])
    if not t or fn is None or peak is None:
        return None
    under = [(name, s, n) for name, path, s, n in t["ops"]
             if path and all(c in path for c in contains)]
    seconds = sum(s for _, s, _ in under)
    calls = sum(n for name, _, n in under if kernel in name)
    if not seconds or not calls:
        return None
    flops, nbytes = fn(run["cfg"], run["wl"])
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least * calls / seconds
