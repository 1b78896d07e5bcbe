"""Process start to the window's opening: imports, TPU start, engine build,
warm-up of the cell's programs, the reference probe, the load's ramp."""


def read(ctx):
    return ctx["setup_s"]
