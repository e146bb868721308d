"""small_bucket_share (program counter:
``res.counters.small_bucket_iters``): the share of event-loop iterations
whose compacted stages ran on the small bucket tier (64 flows) rather
than on the watermark, summed over the lanes of the window's calls
over their summed n_events, in percent."""
from bench.drivers import common


def read(ctx):
    share = common.counter_per_event(ctx["calls"], "small_bucket_iters")
    return None if share is None else 100.0 * share
