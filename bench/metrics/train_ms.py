"""Device milliseconds per round of cohort training: the events of the
jitted program named ``_cohort_train`` in the traced window, over the
rounds completed in it.  Moves ``round_s``."""

UNIT = "ms/round"


def read(ctx):
    t = ctx["trace"]
    seconds = (t or {}).get("programs", {}).get("_cohort_train")
    if not seconds or not ctx["rounds"]:
        return None
    return 1000.0 * seconds / ctx["rounds"]
