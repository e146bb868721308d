"""vm_lifecycle_us (device trace): device self time of the ``vm_lifecycle``
stage (the Fig. 6 VM state transitions), per event-loop iteration of the
traced slice, in microseconds; on several chips the mean over them."""
from bench import stage_reduce


def read(ctx):
    return stage_reduce.stage_us(ctx["traced"], "vm_lifecycle")
