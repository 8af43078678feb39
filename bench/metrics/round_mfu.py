"""The whole round's share of the chip's peak: model FLOPs of the rounds
completed in the traced window (``flops/<name>.py``, recomputation not
counted) over the window, over the peak FLOP/s of ``peaks.json``.
Moves ``round_s``; bounds any kernel's gain end to end."""

UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or not ctx["rounds"]:
        return None
    achieved = ctx["flops"]["total"] * ctx["rounds"] / t["window_s"]
    return 100.0 * achieved / (ctx["chips"] * ctx["peaks"]["flops_per_s"])
