"""Shared by the `device_idle_pct.*` readers."""


def idle_pct(ctx):
    """100 x (1 - busy / window), busy the union of the device's program
    intervals in the traced window, averaged over the chips."""
    if ctx.trace is None or not ctx.trace["window_s"]:
        return None
    busy = sum(d["busy_s"] for d in ctx.trace["devices"]) / len(ctx.trace["devices"])
    return 100 * (1 - busy / ctx.trace["window_s"])
