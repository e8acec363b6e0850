"""Per-layer metrics from ``jax.monitoring`` events."""

from chipbench import record


def cache_misses(run: dict):
    """Persistent-compilation-cache misses over the whole run (an entry
    written counts as a miss, as does a helper under the cache's minimum
    compile time, which is never kept)."""
    return run["counters"].count(record.CACHE_MISS_EVENT)


def compiles_in_window(run: dict):
    """Programs compiled or loaded between window open and close."""
    return run["counters"].count(record.COMPILE_EVENT, run["loop"]["window"])
