"""What the engine calls its work in a profiler trace.

Two kinds of names, both visible in any ``jax.profiler.trace`` capture
(Perfetto, TensorBoard's profile plugin) and both free when no profiler
is running:

* **Stage scopes** (device): ``jax.named_scope`` around each stage of the
  event loop, the pre-loop management pass, the stream step's phases and
  each registered policy body.  A scope is HLO metadata only — it lands in
  every op's ``op_name`` path (``jit(_simulate_jit)/while/body/advance/…``)
  and leaves the compiled computation as it was.
* **Host spans** (host): ``jax.profiler.TraceAnnotation`` around the host
  wrappers' phases, on the profiler's clock, so a device idle gap can be
  matched with what the host was doing in it.

Loop counts (fill, label and queue-serve rounds; event-gate openings) are
not trace events: they are plain values of the result
(:class:`repro.core.loop.state.LoopCounters`, ``res.counters``).
"""
from __future__ import annotations

import jax

# Device scopes: one per stage of loop.driver.STAGES, in that order.
STAGE_SCOPES = ("advance", "observe", "vm_lifecycle", "pm_power",
                "pm_sched", "vm_sched")
TERMINATION = "termination"
MANAGEMENT_PASS = "management_pass"
STREAM_INSERT = "stream_insert"
STREAM_REPLAY = "stream_replay"
STREAM_FLUSH = "stream_flush"
# Every scope a device op can be attributed to.  Policy bodies are scoped
# by their registry name inside pm_sched / vm_sched, one level down.
SCOPES = STAGE_SCOPES + (TERMINATION, MANAGEMENT_PASS, STREAM_INSERT,
                         STREAM_REPLAY, STREAM_FLUSH)

# Host spans.  Each host entry point opens ``repro.<entry>``; inside it:
LAUNCH = "repro.launch"                # the jitted call is dispatched
COMPACT_CHECK = "repro.compact_check"  # the host waits for the overflow flag
DENSE_REPLAY = "repro.dense_replay"    # the replay with compaction off
SHARD_PAD = "repro.shard.pad"          # pad the batch to the shard count
SHARD_UNPAD = "repro.shard.unpad"      # slice the pad lanes off
STREAM_INIT = "repro.stream.init"      # the empty carry and first window
STREAM_WINDOW = "repro.stream.window"  # one window step
STREAM_NEXT_WINDOW = "repro.stream.next_window"  # fetch the next window
STREAM_ASSEMBLE = "repro.stream.assemble"  # flushes onto the task axis

scope = jax.named_scope
span = jax.profiler.TraceAnnotation


def entry(name: str):
    """The span of one call of the host entry point ``name``."""
    return span(f"repro.{name}")
