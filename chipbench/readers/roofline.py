"""A kernel's share of its roofline, from the reduced trace's device
time of the kernel's operations and the family's closed-form counts."""


def kernel_share(run: dict, *, contains: list, counts: str):
    """In percent: the least time the chip could take for the kernel's
    calls of the traced steps over the summed device time of the
    operations whose path holds every entry of ``contains``. The least
    time of one call is the larger of operations / peak FLOP/s and
    bytes / peak bytes/s; both counts and the calls a step come from the
    family's function ``counts(cfg, wl)``, closed forms that never look
    at the trace, so a count that is too high reads over 100%. A
    neighbour's fusion that carries the path of one of the kernel's
    instructions adds its time to the denominator. Without a trace,
    without paths, with no such operation (the kernel is not on the
    path), a family without the function or a device without a peak it
    returns nothing."""
    t, fn = run["trace"], getattr(run["family"], counts, None)
    peak = run["peaks"].get(run["device"]["kind"])
    if not t or fn is None or peak is None:
        return None
    seconds = sum(s for _, path, s, _ in t["ops"]
                  if path and all(c in path for c in contains))
    if not seconds:
        return None
    flops, nbytes, calls_per_step = fn(run["cfg"], run["wl"])
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least * calls_per_step * t["steps"] / seconds
