"""Process start to the first timed request: imports, payload, compiles, warm-up."""


def read(ctx):
    return ctx.setup_s
