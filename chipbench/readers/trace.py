"""Per-layer metrics from the reduced profiler trace. Without a trace
(the capture failed, or found no device operation) they return nothing."""


def device_step_ms(run: dict):
    t = run["trace"]
    return 1e3 * t["busy_s_device0"] / t["steps"] if t else None


def idle_share(run: dict):
    t = run["trace"]
    return 100.0 * (1 - t["busy_s_device0"] / t["window_s"]) if t else None


def allreduce_ms(run: dict):
    t = run["trace"]
    if not t or not t["allreduce_s_device0"]:
        return None
    return 1e3 * t["allreduce_s_device0"] / t["steps"]
