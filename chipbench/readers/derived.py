"""Per-layer metrics computed from the run's rate and the peaks table."""


def mfu(run: dict):
    """Closed-form training operations per image x images per second per
    chip of this run / the chip's bf16 peak, in percent. A device that
    is not in ``peaks.json`` is an error; the CPU of the rehearsal has
    no peak and reports nothing."""
    device = run["device"]
    if device["platform"] == "cpu":
        return None
    peak = run["peaks"][device["kind"]]["bf16_flops_per_s"]
    flops = run["family"].train_flops_per_image(run["cfg"])
    return 100.0 * flops * run["loop"]["metrics"]["img_s_chip"] / peak
