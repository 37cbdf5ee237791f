"""``device_idle_share``: 1 - (union of the device's operation intervals) /
(traced window), in percent. Source: device trace. Moves ``round_s``."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
