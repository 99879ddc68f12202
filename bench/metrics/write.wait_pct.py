"""Share of `LZ4Engine.compress` the host spends waiting on the device.

The program's spans: `compress.wait` (the sync on each micro-batch's sizes)
over `compress.total`.
"""


def read(ctx):
    total = ctx.span_s("compress.total")
    return 100 * ctx.span_s("compress.wait") / total if total else None
