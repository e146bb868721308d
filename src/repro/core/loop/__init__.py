"""The engine's staged subsystem pipeline (DESIGN.md §5).

The single monolithic ``lax.while_loop`` body of the pre-PR 4 engine is
decomposed into pure stage functions over an explicit state protocol —
one module per subsystem:

========================  ===================================================
:mod:`.state`             :class:`CloudState` / :class:`StageCtx` protocol,
                          entity constants
:mod:`.advance`           unified resource sharing + clock-to-horizon (§3.1/2)
:mod:`.observe`           the meter-stack observation hook (§3.3, PR 2)
:mod:`.lifecycle`         VM state machine, Fig. 6 (incl. migration arrival)
:mod:`.power`             PM power-state transitions (Table 1/2, Fig. 5)
:mod:`.pm_sched`          PM policy hook: registry dispatch (DESIGN.md §6)
:mod:`.vm_sched`          VM policy hook: registry dispatch + the shared
                          queue-serving machinery
:mod:`.migrate`           the shared masked live-migration primitive
:mod:`.driver`            stage composition, progress guard, termination
========================  ===================================================

Every stage is ``stage(ctx, st) -> (ctx, st)``: pure, masked-vectorised,
``vmap``/``shard_map``-compatible, and bit-identical in composition to the
pre-refactor monolithic body for the pre-existing scheduler codes.  The
policies themselves — always-on/on-demand/consolidate/defrag/evacuate PM
state schedulers, first-fit/non-queuing/smallest-first VM schedulers —
live in :mod:`repro.sched.policies` and reach the loop only through the
open registry (:mod:`repro.sched.registry`): the core knows no policy by
name.
"""
from .driver import (  # noqa: F401
    LANE_AXIS, STAGES, lane_carry, make_body, management_pass, termination)
from .state import (  # noqa: F401
    BIG, KIND_MIGRATE, TASK_ACTIVE, TASK_DONE, TASK_PENDING, TASK_REJECTED,
    CloudState, StageCtx)
