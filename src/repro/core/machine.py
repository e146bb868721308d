"""Physical/virtual machine state machines (paper §3.4.2-3.4.3, Fig. 5-6).

The vectorized cloud engine (engine.py) keeps one dense slot table per
entity kind; this module defines the state encodings, the legal-transition
table (used by tests and by the engine's masked updates) and small pure
helpers shared by the engine and the schedulers.

Design note (DESIGN.md §2): DISSECT-CF's Java PMs/VMs are objects with
callbacks; here a machine is a row index and a state code, and every state
transition is a masked vector update inside the event-horizon loop.

VM slots own exactly **one active resource consumption at a time**
(image transfer -> boot work -> the user task -> (opt) migration transfer).
This matches the paper's own evaluation protocol ("when the task was
completed its hosting VM was also terminated") and lets the engine rewrite
the consumption slot in place instead of allocating, which is what makes the
whole state machine vectorizable.  Arbitrary consumption graphs (several
flows per entity) remain available through :mod:`repro.core.sharing`.
"""
from __future__ import annotations

import jax.numpy as jnp

# --- VM states (paper Fig. 6) ------------------------------------------------
VM_FREE = 0               # "destroyed" / slot unused
VM_INITIAL_TRANSFER = 1   # image moving to hosting location
VM_STARTUP = 2            # boot-up consumptions running
VM_RUNNING = 3            # serving its task
VM_SHUTDOWN = 4           # image staged, no resources held (pre-staging)
VM_SUSPEND_TRANSFER = 5   # memory state serialising
VM_MIGRATING = 6          # serialized state moving between PMs
VM_SUSPENDED = 7          # image + memory state stored
VM_RESUME_TRANSFER = 8    # memory state reloading
VM_ALLOCATED = 9          # resource allocation held, VM not yet bound (§3.4.2)
N_VM_STATES = 10

# Legal VM transitions (from, to); identity loops are implicit.
VM_TRANSITIONS = frozenset({
    (VM_FREE, VM_ALLOCATED),
    (VM_FREE, VM_INITIAL_TRANSFER),
    (VM_ALLOCATED, VM_INITIAL_TRANSFER),
    (VM_ALLOCATED, VM_FREE),                 # allocation expired (§3.4.2)
    (VM_INITIAL_TRANSFER, VM_SHUTDOWN),
    (VM_INITIAL_TRANSFER, VM_STARTUP),
    (VM_SHUTDOWN, VM_STARTUP),
    (VM_STARTUP, VM_RUNNING),
    (VM_RUNNING, VM_FREE),                   # task done -> destroy
    (VM_RUNNING, VM_SUSPEND_TRANSFER),
    (VM_SUSPEND_TRANSFER, VM_SUSPENDED),
    (VM_SUSPEND_TRANSFER, VM_MIGRATING),     # suspend was for migration
    (VM_MIGRATING, VM_RESUME_TRANSFER),
    (VM_SUSPENDED, VM_RESUME_TRANSFER),
    (VM_RESUME_TRANSFER, VM_RUNNING),
})

# VM states that hold a resource allocation on their PM (cores reserved).
VM_HOLDS_CORES = (VM_ALLOCATED, VM_INITIAL_TRANSFER, VM_STARTUP, VM_RUNNING,
                  VM_SUSPEND_TRANSFER, VM_RESUME_TRANSFER)
# VM states whose own CPU spreader must be performing.
VM_CPU_ACTIVE = (VM_STARTUP, VM_RUNNING, VM_SUSPEND_TRANSFER,
                 VM_RESUME_TRANSFER)

# --- PM power states: re-exported from energy.py (paper Table 1/2) ----------
from .energy import PM_OFF, PM_RUNNING, PM_SWITCHING_OFF, PM_SWITCHING_ON  # noqa: E402


def vm_holds_cores(vstage: jnp.ndarray) -> jnp.ndarray:
    m = jnp.zeros_like(vstage, dtype=bool)
    for s in VM_HOLDS_CORES:
        m = m | (vstage == s)
    return m


def vm_cpu_active(vstage: jnp.ndarray) -> jnp.ndarray:
    m = jnp.zeros_like(vstage, dtype=bool)
    for s in VM_CPU_ACTIVE:
        m = m | (vstage == s)
    return m


def pm_accepting(pstate: jnp.ndarray) -> jnp.ndarray:
    """PMs that can receive new VM allocations right now."""
    return pstate == PM_RUNNING


def vms_per_pm(mask: jnp.ndarray, vm_host: jnp.ndarray,
               n_pm: int) -> jnp.ndarray:
    """i32[P] — how many VM slots selected by ``mask`` each PM hosts; a
    host index outside ``[0, n_pm)`` counts nowhere.

    A one-hot compare-and-sum, not a ``segment_sum``: the TPU compiler
    aborts (``scatter_emitter`` operand check) on a scatter whose indices
    and updates are one buffer — what a count over the initial state folds
    to, where every slot is free and every ``vm_host`` is 0."""
    on = vm_host[None, :] == jnp.arange(n_pm, dtype=vm_host.dtype)[:, None]
    return jnp.sum(on & mask[None, :], axis=1, dtype=jnp.int32)


def pm_future_capacity(pstate: jnp.ndarray) -> jnp.ndarray:
    """PMs that will be able to serve soon (running or booting) — used by the
    on-demand PM scheduler to decide whether more machines must be woken."""
    return (pstate == PM_RUNNING) | (pstate == PM_SWITCHING_ON)


class SpreaderLayout:
    """Index arithmetic for the engine's flat spreader space.

    Layout: ``[cpu: P][net_in: P][net_out: P][repo_out: 1][repo_disk: 1]
    [vm_cpu: V][hidden: P]`` — every resource kind shares one perf vector and
    one fair-share computation (the paper's *unified* model).
    """

    def __init__(self, n_pm: int, n_vm: int):
        self.P = n_pm
        self.V = n_vm
        self.cpu0 = 0
        self.netin0 = n_pm
        self.netout0 = 2 * n_pm
        self.repo_out = 3 * n_pm
        self.repo_disk = 3 * n_pm + 1
        self.vm0 = 3 * n_pm + 2
        self.hidden0 = self.vm0 + n_vm
        self.S = self.hidden0 + n_pm

    def cpu(self, p):
        return self.cpu0 + p

    def netin(self, p):
        return self.netin0 + p

    def netout(self, p):
        return self.netout0 + p

    def vm(self, v):
        return self.vm0 + v

    def hidden(self, p):
        return self.hidden0 + p
