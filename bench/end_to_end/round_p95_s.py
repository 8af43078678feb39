"""95th percentile of all per-round times in the window: the gaps
between consecutive rounds that ``engine.rounds()`` yields (host
clock).  Only for cells with hundreds of rounds in a window."""

import numpy as np

UNIT = "s"


def read(ctx):
    times = ctx["round_times"]
    return float(np.percentile(times, 95)) if len(times) >= 20 else None
