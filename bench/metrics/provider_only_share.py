"""provider_only_share (program counter:
``res.counters.provider_only_solves``): the share of event-loop
iterations whose fair-share solve reduced over the providers alone, no
two active flows naming one consumer, summed over the lanes of the
window's calls over their summed n_events, in percent.  A program
without the counter reports nothing."""
from bench.drivers import common


def read(ctx):
    share = common.counter_per_event(ctx["calls"], "provider_only_solves")
    return None if share is None else 100.0 * share
