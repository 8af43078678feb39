"""Device milliseconds per round of the loss poll: the events of the
jitted program named ``_poll_losses`` in the traced window, over the
rounds completed in it.  Moves ``round_s``."""

UNIT = "ms/round"


def read(ctx):
    t = ctx["trace"]
    seconds = (t or {}).get("programs", {}).get("_poll_losses")
    if not seconds or not ctx["rounds"]:
        return None
    return 1000.0 * seconds / ctx["rounds"]
