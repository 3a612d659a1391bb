"""Process start to the first timed round: imports, data and weights from the seed,
compile or cache read, and the first rounds that warm every shape."""


def read(ctx):
    return ctx["setup_seconds"]
