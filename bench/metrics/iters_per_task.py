"""iters_per_task (program counter: n_events): event-loop iterations per
trace task over the window's calls.  Lanes of a batched call step
together, so a call counts its largest lane's iterations over the tasks
of one lane."""


def read(ctx):
    calls = [c for c in ctx["calls"] if not c.error]
    if not calls:
        return None
    iters = sum(max(c.events) for c in calls)
    tasks = sum(c.tasks / c.lanes for c in calls)
    return iters / tasks
