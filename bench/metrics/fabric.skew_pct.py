"""How unevenly the chips are loaded: 100 x (the busiest chip's busy time
over the mean busy time of the traced chips - 1)."""


def read(ctx):
    if ctx.trace is None or len(ctx.trace["devices"]) < 2:
        return None
    busy = [d["busy_s"] for d in ctx.trace["devices"]]
    mean = sum(busy) / len(busy)
    return 100 * (max(busy) / mean - 1) if mean else None
