"""Device milliseconds per round of the scale-out cohort round: the
events of the jitted program named ``_pod_cohort_round`` (the cohort's
gather across chips, its local steps, the weighted sum and the ``psum``)
in the traced window, averaged over the chips, over the rounds completed
in it.  Moves ``round_s``."""

UNIT = "ms/round"


def read(ctx):
    t = ctx["trace"]
    seconds = (t or {}).get("programs", {}).get("_pod_cohort_round")
    if not seconds or not ctx["rounds"]:
        return None
    return 1000.0 * seconds / ctx["rounds"]
