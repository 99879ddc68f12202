"""The in-graph CRC's share of all device time of the traced restores."""


def read(ctx):
    if ctx.trace is None:
        return None
    total = sum(sum(d["programs"].values()) for d in ctx.trace["devices"])
    return 100 * ctx.program_s("jit_crc32_bytes") / total if total else None
