"""label_rounds (program counter: ``res.counters.label_rounds``):
influence-label propagation rounds of the meters
(``core/influence.py``), in rounds per event-loop iteration: the counter
summed over the lanes of the window's calls, over their summed n_events.
These are each lane's own rounds: under ``vmap`` an inner loop runs
until its slowest lane is done, so the device pays for at least as many."""
from bench.drivers import common


def read(ctx):
    return common.counter_per_event(ctx["calls"], "label_rounds")
