"""observe_us (device trace): device self time of the ``observe`` stage
(utilisation counters, consumption models, meters), per event-loop
iteration of the traced slice, in microseconds; on several chips the
mean over them."""
from bench import stage_reduce


def read(ctx):
    return stage_reduce.stage_us(ctx["traced"], "observe")
