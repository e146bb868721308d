"""The open scheduler-policy registry (DESIGN.md §6).

DISSECT-CF's extensibility pitch is that *new scheduling policies must not
require touching the simulator core*.  This module is that seam: a policy
is a pure stage function

    ``policy(spec, params, ctx, state) -> state``

registered under a **stable integer code** per management layer (``"pm"``
physical-machine state scheduling, ``"vm"`` request dispatching) with
metadata (name, layer, required state fields, whether a PM fleet starts
powered on).  The engine's ``pm_sched`` / ``vm_sched`` loop stages
dispatch over :func:`stage_branches` with ``lax.switch`` on the
``CloudParams.pm_sched`` / ``vm_sched`` code — the code stays *traced
data*, so heterogeneous policy cells still batch through one compiled
``simulate_batch`` program, and registering a policy makes it a
tournament/Pareto/ensemble citizen with no further wiring
(:func:`repro.experiments.tournament.scheduler_grid` builds its axes from
:func:`names`).

Code stability rules (what makes a code "stable"):

* codes are contiguous ``0..N-1`` per layer and are assigned append-only:
  a new policy takes the next free code (or must name exactly it);
* re-using a live code, or re-using a live name, is rejected — results
  keyed by (layer, code) stay comparable across runs;
* only the most recently registered (highest-code) non-builtin policy can
  be unregistered, so the builtin prefix — and any published code — never
  shifts;
* registering or unregistering drops the engine's compiled-program caches
  (the branch list is baked into a traced program, the *code* is not), so
  the next ``simulate``/``simulate_batch`` retraces over the new branch
  list.  Existing codes are guaranteed bit-identical across that retrace:
  ``lax.switch`` only adds a branch, it never changes what the other
  branches compute (tested in ``tests/test_registry.py``).

The builtin policies live in :mod:`repro.sched.policies` and register
themselves through this exact interface — core knows no policy by name.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Callable

LAYERS = ("pm", "vm")


@dataclasses.dataclass(frozen=True)
class Policy:
    """One registered scheduler policy and its metadata."""

    code: int            # stable integer id == CloudParams.{pm,vm}_sched
    name: str            # stable human name (tournament rows, params)
    layer: str           # "pm" | "vm"
    fn: Callable         # pure stage: (spec, params, ctx, state) -> state
    requires: tuple[str, ...] = ()   # the policy's state delta: CloudState
    #                                  fields it may write
    starts_running: bool = False     # PM layer: the fleet boots powered on
    doc: str = ""
    trigger: Callable | None = None  # event-gate: (spec, params, ctx, state)
    #   -> bool scalar.  The loop stage skips the policy body entirely
    #   (lax.cond) whenever this returns False, so it MUST be a *necessary*
    #   condition for the policy to change state — i.e. trigger False
    #   implies the policy is bitwise identity on ``state``.  ``None``
    #   (the default) means "may always act": the policy runs every
    #   iteration, exactly as before triggers existed.  This mirrors the
    #   paper's subscription model (§3.5: schedulers are notified on queue
    #   / machine state changes, they do not poll every tick).


_registry: dict[str, dict[int, Policy]] = {layer: {} for layer in LAYERS}
_builtin_count: dict[str, int] = {}
_loading_builtins = False


def _builtins_loaded() -> None:
    """Called by :mod:`repro.sched.policies` as the *last* statement of its
    import: records the builtin code range, arming the builtin-unregister
    protection.  Keeping this at the end of the package import (rather
    than after an ``import policies`` here) makes the bookkeeping correct
    no matter who triggers the import first — the registry, or a direct
    ``import repro.sched.policies`` whose mid-import re-entry into
    :func:`register` must not record a partial (or empty) count."""
    if not _builtin_count:
        for layer in LAYERS:
            _builtin_count[layer] = len(_registry[layer])


def _ensure_builtins() -> None:
    """Load the builtin policy package once (it registers on import).

    Re-entrant (the builtin modules call :func:`register`, which lands
    back here while the package is mid-import) and exception-safe: the
    builtin count is recorded by :func:`_builtins_loaded` only after the
    *whole* package imported, so a failed import is retried on the next
    call instead of leaving a partial registry that looks complete."""
    global _loading_builtins
    if _builtin_count or _loading_builtins:
        return
    _loading_builtins = True
    try:
        from . import policies  # noqa: F401  (side effect: register())
    finally:
        _loading_builtins = False


def _invalidate_compiled_engines() -> None:
    """Registration changes the branch list baked into traced programs —
    drop every compiled-engine cache so the next call retraces."""
    eng = sys.modules.get("repro.core.engine")
    if eng is not None:
        eng.simulate.clear_cache()
        eng.simulate_batch.clear_cache()
    shard = sys.modules.get("repro.experiments.shard")
    if shard is not None:
        shard._sharded_runner.cache_clear()


def _check_layer(layer: str) -> None:
    if layer not in LAYERS:
        raise ValueError(f"unknown scheduler layer {layer!r}; one of {LAYERS}")


def register(layer: str, name: str, fn: Callable, *, code: int | None = None,
             requires: tuple[str, ...] = (), starts_running: bool = False,
             doc: str = "", trigger: Callable | None = None) -> Policy:
    """Register ``fn`` as a scheduler policy; returns its :class:`Policy`.

    ``code`` defaults to the next free code of the layer; passing a code
    explicitly asserts the stable id the caller expects (anything but the
    next free code is rejected — duplicate codes would silently alias two
    policies, holes would break the dense ``lax.switch`` dispatch).
    ``requires`` declares the policy's state delta — the
    :class:`~repro.core.loop.state.CloudState` fields it may write.  Field
    *names* are validated against the state protocol (what the body
    actually writes is the author's contract to keep).

    ``trigger`` optionally declares the policy's event gate (see
    :class:`Policy`): a cheap necessary condition for the policy to act,
    letting the loop stage skip the body when nothing it reacts to
    happened.  Omit it unless the identity claim genuinely holds.
    """
    _check_layer(layer)
    _ensure_builtins()
    table = _registry[layer]
    next_code = len(table)
    if code is None:
        code = next_code
    if code in table:
        raise ValueError(
            f"duplicate {layer} policy code {code}: already registered as "
            f"{table[code].name!r}; codes are stable and append-only "
            f"(next free: {next_code})")
    if code != next_code:
        raise ValueError(
            f"{layer} policy codes must stay contiguous: next free code is "
            f"{next_code}, got {code}")
    if any(p.name == name for p in table.values()):
        raise ValueError(f"duplicate {layer} policy name {name!r}")
    if not callable(fn):
        raise TypeError(f"policy fn must be callable, got {fn!r}")
    from repro.core.loop.state import CloudState
    unknown = set(requires) - set(CloudState._fields)
    if unknown:
        raise ValueError(
            f"policy {name!r} requires unknown CloudState field(s) "
            f"{sorted(unknown)}; known: {CloudState._fields}")
    if trigger is not None and not callable(trigger):
        raise TypeError(f"policy trigger must be callable, got {trigger!r}")
    policy = Policy(code=code, name=name, layer=layer, fn=fn,
                    requires=tuple(requires), starts_running=starts_running,
                    doc=doc, trigger=trigger)
    table[code] = policy
    _invalidate_compiled_engines()
    return policy


def _builtin_limit(layer: str) -> int:
    """Codes below this are builtin.  While the builtin package is still
    importing the count is unrecorded — treat everything as protected."""
    table = _registry[layer]
    return _builtin_count.get(layer, len(table))


def unregister(layer: str, code_or_name: int | str) -> Policy:
    """Remove a previously registered policy (round-trip for experiments).

    Only the highest-code non-builtin policy may be removed: codes are
    append-only so published codes never shift or get re-used under a
    different meaning mid-process.  A :class:`CloudParams` built while the
    policy existed still *holds* its code; simulating with such a stale
    code after unregistration is undefined (``lax.switch`` clamps it to
    the highest remaining branch) — rebuild params after unregistering."""
    _check_layer(layer)
    _ensure_builtins()
    policy = get(layer, code_or_name)
    table = _registry[layer]
    if policy.code < _builtin_limit(layer):
        raise ValueError(
            f"cannot unregister builtin {layer} policy "
            f"{policy.name!r} (code {policy.code})")
    if policy.code != len(table) - 1:
        raise ValueError(
            f"only the most recently registered {layer} policy can be "
            f"unregistered (highest code {len(table) - 1}, got "
            f"{policy.code}) — codes are append-only")
    del table[policy.code]
    _invalidate_compiled_engines()
    return policy


def get(layer: str, code_or_name: int | str) -> Policy:
    """Look a policy up by stable code or by name."""
    _check_layer(layer)
    _ensure_builtins()
    table = _registry[layer]
    if isinstance(code_or_name, str):
        for p in table.values():
            if p.name == code_or_name:
                return p
        raise KeyError(
            f"unknown {layer} policy {code_or_name!r}; "
            f"registered: {names(layer)}")
    code = int(code_or_name)
    if code not in table:
        raise KeyError(
            f"unknown {layer} policy code {code}; registered: 0..{len(table) - 1}")
    return table[code]


def policies(layer: str) -> tuple[Policy, ...]:
    """Every registered policy of ``layer``, ordered by code."""
    _check_layer(layer)
    _ensure_builtins()
    table = _registry[layer]
    return tuple(table[c] for c in range(len(table)))


def names(layer: str) -> tuple[str, ...]:
    """Registered policy names ordered by code (index == code — the
    successor of the old ``VM_SCHEDULERS``/``PM_SCHEDULERS`` tuples)."""
    return tuple(p.name for p in policies(layer))


def code_of(layer: str, name: str) -> int:
    return get(layer, name).code


def name_of(layer: str, code: int) -> str:
    return get(layer, int(code)).name


def stage_branches(layer: str, ctx) -> tuple[Callable, ...]:
    """The dense branch list the loop stages hand to ``lax.switch``: one
    ``(st) -> st`` callable per code, in code order, each closed over the
    iteration's :class:`~repro.core.loop.state.StageCtx` (the context
    holds the jit-static ``CloudSpec``, so it is captured, not passed as a
    switch operand).  Each branch runs under a ``jax.named_scope`` of its
    policy name, so a profiler trace tells the policies' device time
    apart (:mod:`repro.core.tracing`)."""
    from repro.core import tracing

    def bind(p):
        def branch(st):
            with tracing.scope(p.name):
                return p.fn(ctx.spec, ctx.params, ctx, st)
        return branch

    return tuple(bind(p) for p in policies(layer))


def trigger_branches(layer: str, ctx) -> tuple[Callable, ...]:
    """The event-gate branch list matching :func:`stage_branches`: one
    ``(st) -> bool`` callable per code.  A policy without a declared
    trigger gets a constant-True gate — it runs every iteration."""
    import jax.numpy as jnp

    def bind(p):
        if p.trigger is None:
            return lambda st: jnp.bool_(True)
        return lambda st: jnp.asarray(
            p.trigger(ctx.spec, ctx.params, ctx, st), bool)

    return tuple(bind(p) for p in policies(layer))


def start_running_codes() -> tuple[int, ...]:
    """PM policy codes whose fleets begin powered on (engine init)."""
    return tuple(p.code for p in policies("pm") if p.starts_running)
