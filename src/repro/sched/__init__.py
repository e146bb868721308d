"""Scheduling on top of the core engine.

* :mod:`~repro.sched.registry` — the open scheduler-policy registry the
  engine's ``pm_sched``/``vm_sched`` loop stages dispatch over
  (DESIGN.md §6);
* :mod:`~repro.sched.policies` — the builtin PM/VM policies, registered
  through that interface (core knows none of them by name).

Scheduler comparisons over the registry's grid run through
:mod:`repro.experiments.tournament`.

Kept import-light: ``registry`` is imported by the core loop stages, so
nothing heavy (and nothing that imports the engine) may load here.
"""
from . import registry  # noqa: F401
