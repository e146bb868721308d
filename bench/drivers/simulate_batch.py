"""Entry driver: ``engine.simulate_batch``, one scenario grid per call.

Every lane of the grid replays the same trace of the input pool under its
own scheduler pair and idle-draw scale, vmapped through one program."""
from __future__ import annotations

from bench.drivers import simulate


class Workload(simulate.Workload):
    batched = True

    def entry(self, trace, t_stop):
        return self.engine.simulate_batch(self.spec, trace, self.params,
                                          t_stop=t_stop)
