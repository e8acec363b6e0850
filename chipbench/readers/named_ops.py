"""Per-layer metrics from the device time of operations chosen by their
scope path OR by their own name: a kernel the compiler writes itself
(``lax.ragged_dot`` on the TPU becomes Mosaic kernels named
``ragged-dot-none.N``) carries no ``jax.named_scope`` path in the trace,
only its name, so ``scope.ms_per_step`` and ``roofline.kernel_share``
never see it."""


def _seconds(run: dict, contains: list, names: list, excludes: list):
    """Summed seconds over the traced slice of device 0's operations
    whose path holds every entry of ``contains`` and no string of
    ``excludes``, or whose own name holds one of ``names``; None without
    a trace or without such an operation."""
    t = run["trace"]
    if not t:
        return None
    seconds = [
        s for name, path, s, _ in t["ops"]
        if any(n in name for n in names)
        or (path and all(c in path for c in contains)
            and not any(x in path for x in excludes))]
    return sum(seconds) if seconds else None


def ms_per_step(run: dict, *, contains: list, names: list,
                excludes: list = ()):
    """Milliseconds a step of the traced slice."""
    seconds = _seconds(run, contains, names, excludes)
    return None if seconds is None else 1e3 * seconds / run["trace"]["steps"]


def kernel_share(run: dict, *, contains: list, names: list, counts: str):
    """As ``roofline.kernel_share``, over the operations chosen by path
    or by name, for a kernel whose work differs from step to step: the
    least time the chip could take for the calls of each whole step of
    the traced slice (the larger of operations / peak FLOP/s and bytes /
    peak bytes/s), summed over those steps, over the operations' summed
    device time, in percent. The family's ``counts(cfg, wl, steps)``
    gives (operations, bytes) of each of the slice's ``steps`` steps,
    from closed forms and what the program counted in those very steps,
    never from the trace, so a count that is too high reads over 100%;
    where it has nothing for them (no run kept it), as without a trace,
    such an operation, the function or a peak, the reader returns
    nothing."""
    fn = getattr(run["family"], counts, None)
    peak = run["peaks"].get(run["device"]["kind"])
    seconds = _seconds(run, contains, names, ())
    if fn is None or peak is None or not seconds:
        return None
    by_step = fn(run["cfg"], run["wl"], run["trace"]["steps"])
    if not by_step:
        return None
    least = sum(max(flops / peak["bf16_flops_per_s"],
                    nbytes / peak["hbm_bytes_per_s"])
                for flops, nbytes in by_step)
    return 100.0 * least / seconds
