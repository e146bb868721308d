"""live_flows (program counter: ``res.counters.live_flows``): the active
flows an event-loop iteration starts with, summed over the lanes of the
window's calls over their summed n_events: the mean size of the set the
compacted stages work on."""
from bench.drivers import common


def read(ctx):
    return common.counter_per_event(ctx["calls"], "live_flows")
