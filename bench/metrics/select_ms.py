"""Host milliseconds per round of selection: the benchmark's span
``bench.select`` around the engine's ``select`` hook in the traced
window, over the rounds completed in it.  Moves ``round_s``."""

UNIT = "ms/round"


def read(ctx):
    t = ctx["trace"]
    seconds = (t or {}).get("spans", {}).get("select")
    if not seconds or not ctx["rounds"]:
        return None
    return 1000.0 * seconds / ctx["rounds"]
