"""advance_us (device trace): device self time of the ``advance`` stage
(the fair-share solve, the gather of the live flows, the horizon of the
next event), per event-loop iteration of the traced slice, in
microseconds; on several chips the mean over them."""
from bench import stage_reduce


def read(ctx):
    return stage_reduce.stage_us(ctx["traced"], "advance")
