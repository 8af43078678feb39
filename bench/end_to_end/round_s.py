"""Seconds per federated round: the whole window, from its start to
``block_until_ready`` on the parameters after its last round, over the
whole rounds completed in it (host clock)."""

UNIT = "s/round"


def read(ctx):
    return ctx["window_s"] / ctx["rounds"] if ctx["rounds"] else None
