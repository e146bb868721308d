"""Entry driver: ``engine.simulate_batch_sharded``, one scenario grid per
call with its lanes spread over the cell's chips by ``shard_map``.

The inputs stay uncommitted (made with ``jnp.asarray``, not placed on
one device): the program lays the grid out over the chips itself."""
from __future__ import annotations

from bench.drivers import common, simulate


class Workload(simulate.Workload):
    batched = True

    def setup(self):
        from repro.core import engine
        self.engine = engine
        self.spec, self.params = common.engine_cloud(self.cell.config,
                                                     self.lane_list)
        self.host = self.traces()
        self.pool = [common.trace_of(h) for h in self.host]
        self.n_tasks = int(self.cell.traffic["n_tasks"])

    def entry(self, trace, t_stop):
        return self.engine.simulate_batch_sharded(
            self.spec, trace, self.params, t_stop=t_stop,
            devices=list(self.devices))
