"""serve_rounds (program counter: ``res.counters.serve_rounds``): queue-
serving rounds of the VM policy (``loop/vm_sched.py`` ``serve_queue``),
in rounds per event-loop iteration: the counter summed over the lanes of
the window's calls, over their summed n_events.  These are each lane's
own rounds: under ``vmap`` an inner loop runs until its slowest lane is
done, so the device pays for at least as many."""
from bench.drivers import common


def read(ctx):
    return common.counter_per_event(ctx["calls"], "serve_rounds")
