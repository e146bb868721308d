"""dense_replays (program counter): compaction-bucket overflows that made
the program replay a call densely (its RuntimeWarning), summed over the
window's calls."""


def read(ctx):
    return sum(c.dense_replays for c in ctx["calls"])
