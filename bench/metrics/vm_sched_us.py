"""vm_sched_us (device trace): device self time of the ``vm_sched`` stage,
its VM policy bodies included (under ``vmap`` every lane pays for every
policy), per event-loop iteration of the traced slice, in microseconds;
on several chips the mean over them."""
from bench import stage_reduce


def read(ctx):
    return stage_reduce.stage_us(ctx["traced"], "vm_sched")
