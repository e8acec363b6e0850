"""Per-layer metrics from the router state a mixture-of-experts family
kept when the harness last asked it for its moving state."""


def load_max_over_mean(run: dict):
    """The worst expert layer's largest cumulative load over its mean
    load, from the cumulative loads of every expert in the trainer's
    state after the run: 1 is an even spread. ``run.py`` hands a reader
    no trainer; the family's ``moving_state(dp)``, which it calls after
    the loop, keeps the loads it read as ``LAST_LOADS`` ((layers,
    experts) arrays, one a block). A family without them, or one that
    was never asked, reads as nothing."""
    loads = getattr(run["family"], "LAST_LOADS", None)
    ratios = [float(layer.max() / layer.mean())
              for block in loads or () for layer in block if layer.mean() > 0]
    return max(ratios) if ratios else None
