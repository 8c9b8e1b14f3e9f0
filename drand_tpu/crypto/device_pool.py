"""Device pool: inventory, per-handle device groups, and the pool-wide
round-axis sharding (ISSUE 11, ROADMAP item 2 serving path).

Everything the verify plane served before this module ran on ONE device
while `__graft_entry__.dryrun_multichip` proved an 8-device mesh is
placeable.  The pool turns that hardware multiplier into two serving-path
shapes:

  * **Per-handle device groups.**  The visible devices are partitioned
    into `n_groups` groups (`Config.verify_device_groups` /
    `DRAND_VERIFY_DEVICE_GROUPS`; 0 = AUTO, one group per device) and
    every `VerifyService` handle is assigned one — sticky chain→device
    affinity, least-loaded at assignment, so k chips run k concurrent
    depth-k dispatch windows instead of sharing one stream.  A group
    whose device faults is marked and new work avoids it; its handles
    fail over to a healthy sibling group before falling to host.
  * **Pool-wide round-axis sharding.**  One persistent Mesh/NamedSharding
    over ALL devices for huge batches (catch-up sync, integrity scans,
    strict-walk sweeps) — the maxtext-style data-axis shape from the
    SNIPPETS.md pjit/mesh exemplars, built once and reused forever.

This module is the ONLY place in the package allowed to call
`jax.devices()` / `jax.local_devices()` (tpu-vet `verifier` checker): one
cached inventory means every consumer sees the same device list, and the
platform each group runs on (`DeviceGroup.platform`) is read from it.

jax is imported lazily; with no jax at all the pool degenerates to one
deviceless group, so the host-fallback paths keep their stream without
touching an accelerator stack.
"""

import os
import threading

from ..common import make_lock
from typing import Dict, List, Optional, Tuple

DEFAULT_GROUPS = int(os.environ.get("DRAND_VERIFY_DEVICE_GROUPS", "0"))

GROUP_HEALTHY = "healthy"
GROUP_FAULTED = "faulted"
GROUP_PROBING = "probing"

_inventory_lock = make_lock()
_inventory: Optional[list] = None


def jax_devices() -> list:
    """The sanctioned device-enumeration call site (cached for the
    process: `jax.devices()` is stable after backend init).  [] when jax
    is not installed; a backend that fails to initialize (e.g. another
    process holds the chip) raises, it is never read as "no devices"."""
    global _inventory
    with _inventory_lock:
        if _inventory is not None:
            return list(_inventory)
    try:
        import jax
    except ImportError:
        return []
    devs = list(jax.devices())
    with _inventory_lock:
        if _inventory is None:
            _inventory = devs
        return list(_inventory)


_compiling_lock = make_lock()
_compiling: Dict[int, int] = {}      # thread ident -> nesting depth


class compiling:
    """Context: the calling thread is tracing/compiling a device program
    (batch.run_program around a program's first call).  A cold compile
    of a verify program is minutes of host work on the chip's machine
    (PR 21 rehearsal); the verify service's watchdog reads this mark and
    does not take the compile for a hung device."""

    def __enter__(self):
        me = threading.get_ident()
        with _compiling_lock:
            _compiling[me] = _compiling.get(me, 0) + 1
        return self

    def __exit__(self, *exc):
        me = threading.get_ident()
        with _compiling_lock:
            left = _compiling.get(me, 1) - 1
            if left:
                _compiling[me] = left
            else:
                _compiling.pop(me, None)
        return False


def thread_compiling(ident) -> bool:
    """True while thread `ident` is inside a `compiling` context."""
    with _compiling_lock:
        return ident in _compiling


def _reset_inventory_for_tests(devices=None) -> None:
    """Test hook: override (or clear) the cached inventory."""
    global _inventory
    with _inventory_lock:
        _inventory = list(devices) if devices is not None else None


def build_round_sharding(devices):
    """The one place the round-axis placement is constructed: None for
    no devices (nothing to pin), `SingleDeviceSharding` for one, a
    round-axis `Mesh`/`NamedSharding` for several.  Group shardings,
    the pool-wide mesh and `BatchBeaconVerifier._placement` all build
    through here so the axis name and single-vs-multi rules cannot
    drift apart."""
    devices = list(devices)
    if not devices:
        return None
    if len(devices) == 1:
        from jax.sharding import SingleDeviceSharding
        return SingleDeviceSharding(devices[0])
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    return NamedSharding(Mesh(np.array(devices), ("round",)),
                         PartitionSpec("round"))


class DeviceGroup:
    """One failure/dispatch domain: a slice of the device inventory with
    a lazily-built persistent placement (SingleDeviceSharding for one
    device, NamedSharding over a round-axis mesh for several, None for a
    deviceless host group)."""

    __slots__ = ("gid", "devices", "state", "faulted_at", "probe_backend",
                 "probe_sample", "_sharding", "_sharding_built")

    def __init__(self, gid: int, devices: list):
        self.gid = gid
        self.devices = list(devices)
        self.state = GROUP_HEALTHY
        self.faulted_at: Optional[float] = None
        # the canary context stashed when the group faults: the backend
        # that was serving on it and its last known-good 1-lane sample
        self.probe_backend = None
        self.probe_sample = None
        self._sharding = None
        self._sharding_built = False

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def platform(self) -> str:
        """What this group's work runs on: the devices' platform ("tpu",
        "cpu", ...) or "host" for the deviceless group."""
        if not self.devices:
            return "host"
        return getattr(self.devices[0], "platform", "unknown")

    def sharding(self):
        """Persistent placement for this group, built once (the
        per-dispatch mesh construction this PR retires was pure
        overhead)."""
        if self._sharding_built:
            return self._sharding
        self._sharding = build_round_sharding(self.devices)
        self._sharding_built = True
        return self._sharding

    def __repr__(self):
        return (f"DeviceGroup(gid={self.gid}, devices={self.n_devices}, "
                f"state={self.state})")


class DevicePool:
    """Owns the device inventory and the handle→group assignment map.

    Assignment is sticky (chain→device affinity: a chain's compiled
    programs live on its group's devices) and least-loaded among HEALTHY
    groups at creation time; `release` drops an assignment so handle
    churn rebalances — the next assignment fills the emptied group.
    """

    def __init__(self, n_groups: int = 0, devices: Optional[list] = None):
        devs = list(devices) if devices is not None else jax_devices()
        want = int(n_groups) if n_groups and int(n_groups) > 0 \
            else (DEFAULT_GROUPS or 0)
        if want <= 0:
            want = max(1, len(devs))        # AUTO: one group per device
        want = max(1, min(want, max(1, len(devs))))
        self.groups: List[DeviceGroup] = []
        if devs:
            base, extra = divmod(len(devs), want)
            lo = 0
            for g in range(want):
                hi = lo + base + (1 if g < extra else 0)
                self.groups.append(DeviceGroup(g, devs[lo:hi]))
                lo = hi
        else:
            self.groups.append(DeviceGroup(0, []))  # deviceless host group
        self._devices = devs
        self._assignments: Dict[Tuple, int] = {}
        # keys whose handles never dispatch on the group's devices (host
        # fallback handles): they keep a stream affinity but must not
        # weigh on the least-loaded placement of real device chains
        self._weightless: set = set()
        # tenant-aware placement (core/tenancy.py, ISSUE 15): per-key WFQ
        # weight (placement is weight-PROPORTIONAL: a weight-3 tenant's
        # chain loads a group 3x as much as a weight-1 chain, so the
        # least-loaded choice spreads heavy tenants first) and the key's
        # tenant label for anti-affinity + the snapshot
        self._weights: Dict[Tuple, float] = {}
        self._tenants: Dict[Tuple, str] = {}
        self._lock = make_lock()
        self._pool_sharding = None
        self._pool_sharding_built = False

    # -- inventory ------------------------------------------------------------

    @property
    def n_devices(self) -> int:
        return len(self._devices)

    @property
    def n_groups(self) -> int:
        return len(self.groups)

    @property
    def platform(self) -> str:
        return self.groups[0].platform

    def group(self, gid: int) -> DeviceGroup:
        return self.groups[gid]

    def healthy_groups(self) -> List[DeviceGroup]:
        return [g for g in self.groups if g.state == GROUP_HEALTHY]

    def pool_sharding(self):
        """ONE persistent NamedSharding over the round axis spanning the
        FULL pool — the huge-batch (catch-up sync / integrity scan) path.
        None with fewer than 2 devices: single-device runs gain nothing
        from an SPMD-partitioned program."""
        if self._pool_sharding_built:
            return self._pool_sharding
        sh = build_round_sharding(self._devices) \
            if len(self._devices) >= 2 else None
        self._pool_sharding = sh
        self._pool_sharding_built = True
        return sh

    # -- assignment -----------------------------------------------------------

    def _loads_locked(self) -> Dict[int, float]:
        loads = {g.gid: 0.0 for g in self.groups}
        for key, gid in self._assignments.items():
            if key not in self._weightless:
                loads[gid] = loads.get(gid, 0.0) \
                    + self._weights.get(key, 1.0)
        return loads

    def assign(self, key, weigh: bool = True, tenant: Optional[str] = None,
               weight: float = 1.0, pin: Optional[int] = None,
               anti_affinity: bool = False) -> DeviceGroup:
        """Sticky least-loaded assignment, weight-proportional.  Healthy
        groups are preferred; with every group faulted the least-loaded
        one is used anyway (the service's own failover ladder handles
        the fault).  `weigh=False` grants a stream affinity without
        counting toward group load — host-fallback handles never
        dispatch on the devices, so they must not push device chains off
        a group.

        Tenant hints (core/tenancy.py `placement_for_pk`): `weight`
        scales this key's contribution to group load, `pin` forces a
        specific group (premium isolation; ignored when out of range, and
        a FAULTED pinned group still pins — its failover is the
        service's ladder, not a silent placement change), and
        `anti_affinity` prefers a healthy group no OTHER tenant's keys
        occupy when one exists."""
        with self._lock:
            gid = self._assignments.get(key)
            if gid is not None:
                return self.groups[gid]
            if tenant is not None:
                self._tenants[key] = tenant
            self._weights[key] = max(0.0, float(weight))
            if pin is not None and 0 <= pin < len(self.groups):
                self._assignments[key] = pin
                if not weigh:
                    self._weightless.add(key)
                return self.groups[pin]
            loads = self._loads_locked()
            candidates = [g for g in self.groups
                          if g.state == GROUP_HEALTHY] or self.groups
            if anti_affinity and tenant is not None:
                empty = [g for g in candidates
                         if not any(gid == g.gid
                                    and self._tenants.get(k) != tenant
                                    and k not in self._weightless
                                    for k, gid in self._assignments.items())]
                if empty:
                    candidates = empty
            best = min(candidates, key=lambda g: (loads[g.gid], g.gid))
            self._assignments[key] = best.gid
            if not weigh:
                self._weightless.add(key)
            return best

    def reassign(self, key) -> Optional[DeviceGroup]:
        """Move `key` to the least-loaded HEALTHY group other than its
        current one (group failover: handle → healthy sibling).  None
        when no healthy sibling exists — the caller falls to host."""
        with self._lock:
            cur = self._assignments.get(key)
            loads = self._loads_locked()
            candidates = [g for g in self.groups
                          if g.state == GROUP_HEALTHY and g.gid != cur]
            if not candidates:
                return None
            best = min(candidates, key=lambda g: (loads[g.gid], g.gid))
            self._assignments[key] = best.gid
            return best

    def place(self, key, gid: int) -> None:
        """Force an assignment (the migrate-revert path: a failed
        sibling rebuild must put the affinity back where the backend
        actually still lives, or load accounting and stats drift)."""
        with self._lock:
            self._assignments[key] = gid

    def release(self, key) -> None:
        """Drop an assignment (handle churn): the next `assign` call
        rebalances into the emptied group."""
        with self._lock:
            self._assignments.pop(key, None)
            self._weightless.discard(key)
            self._weights.pop(key, None)
            self._tenants.pop(key, None)

    def loads(self) -> Dict[int, float]:
        with self._lock:
            return self._loads_locked()

    def gid_of(self, key) -> Optional[int]:
        with self._lock:
            return self._assignments.get(key)

    def snapshot(self) -> dict:
        """Per-group view for stats()/health: device count, state,
        weighted handle load, and which tenants' chains live there."""
        with self._lock:
            loads = self._loads_locked()
            tenants = {g.gid: set() for g in self.groups}
            for key, gid in self._assignments.items():
                t = self._tenants.get(key)
                if t is not None and key not in self._weightless:
                    tenants.setdefault(gid, set()).add(t)
        return {g.gid: {"devices": g.n_devices, "platform": g.platform,
                        "state": g.state,
                        "handles": loads.get(g.gid, 0),
                        **({"tenants": sorted(tenants[g.gid])}
                           if tenants.get(g.gid) else {})}
                for g in self.groups}
