"""Process start to window start: weights, encode, engine, compiles or
cache loads, warm-up and the ramp of the cell's own traffic."""


def read(ctx):
    return ctx.setup_s
