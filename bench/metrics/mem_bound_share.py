"""mem_bound_share (program counter: ``res.counters.mem_bound``): the
dispatches whose first fit on cores and memory chose another PM than a
first fit on cores alone, over the trace tasks of the window's calls
(every lane's), in percent."""


def read(ctx):
    calls = [c for c in ctx["calls"] if not c.error]
    lanes = [a for c in calls for a in c.answers]
    tasks = sum(c.tasks for c in calls)
    if not tasks or any("mem_bound" not in a.get("counters", {})
                        for a in lanes):
        return None
    return 100.0 * sum(a["counters"]["mem_bound"] for a in lanes) / tasks
