"""dense_pass_share (program counter: ``res.counters.dense_iters``): the
share of event-loop iterations whose compacted stages ran dense, in the
same program, because the active set outgrew the largest bucket tier,
summed over the lanes of the window's calls over their summed n_events,
in percent."""
from bench.drivers import common


def read(ctx):
    share = common.counter_per_event(ctx["calls"], "dense_iters")
    return None if share is None else 100.0 * share
