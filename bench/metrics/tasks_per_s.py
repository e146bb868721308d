"""tasks_per_s (host clock): trace tasks replayed to completion or
rejection, summed over every lane of every call, over the window's wall
time from the first call's start to the last call's end."""


def read(ctx):
    calls = ctx["calls"]
    window = calls[-1].end - calls[0].start
    return sum(c.tasks for c in calls) / window
