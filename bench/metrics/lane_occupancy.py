"""lane_occupancy (program counter: n_events): useful lane-iterations of
the window's batched calls over the lane-iterations paid for, since every
lane steps until the longest is done:
sum of lane n_events / (lanes x largest lane n_events), in percent."""


def read(ctx):
    calls = [c for c in ctx["calls"] if not c.error and c.lanes > 1]
    if not calls:
        return None
    used = sum(sum(c.events) for c in calls)
    paid = sum(c.lanes * max(c.events) for c in calls)
    return 100.0 * used / paid if paid else None
