"""Per-layer metrics from the benchmark's own host-clock spans."""


def mean_ms(run: dict, *, span: str):
    """Mean duration, in milliseconds, of the named span over the steps
    of the measured window."""
    durations = run["spans"].durations(span, run["loop"]["window"])
    return 1e3 * sum(durations) / len(durations) if durations else None
