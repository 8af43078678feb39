"""Set-up: seconds from process start to the start of the measured
window (imports, data from the seed, engine build with partition,
clustering and Hellinger matrix, weights, warm-up rounds and their
compiles), on the host clock."""

UNIT = "s"


def read(ctx):
    return ctx["setup_s"]
