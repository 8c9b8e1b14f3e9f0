"""verifier-discipline: all device verification flows through the
resident verify service, and all device ENUMERATION through its pool.

The verify service (crypto/verify_service.py) exists so the device sees
ONE owner — coalesced canonical batches, priority lanes, per-handle
device groups over a persistent pool — instead of per-consumer ad-hoc
dispatch.  That architecture only holds if consumers cannot quietly
regrow private dispatch paths, so two rules:

  * constructing `BatchBeaconVerifier` directly is banned outside
    `crypto/` (the service and the crypto package internals own the
    pipelines).  Everything else gets a `VerifyService.handle(...)` (or
    passes `device=False` for the jax-free `HostBatchVerifier` fallback
    behind the same submit API).
  * calling `jax.devices()` / `jax.local_devices()` is banned outside
    `crypto/device_pool.py` — the pool owns inventory, group layout and
    the pool-wide mesh, and one cached call site means every consumer
    sees the same inventory.  Bench/dryrun tooling outside the package
    carries its own justified suppressions.
"""

import ast
from typing import Iterator

from ..core import Finding
from ..symbols import ModuleInfo, dotted

TARGET = "BatchBeaconVerifier"

# modules allowed to construct the raw verifier: the crypto package owns
# the device pipelines and the service that fronts them
ALLOWED_PREFIX = "crypto/"

# the one sanctioned device-enumeration call site (the pool)
DEVICE_CALLS = {"jax.devices", "jax.local_devices"}
POOL_MODULE = "crypto/device_pool.py"


class VerifierChecker:
    name = "verifier"
    description = ("direct BatchBeaconVerifier construction outside "
                   "crypto/ (bypasses the resident verify service) and "
                   "jax device enumeration outside crypto/device_pool.py")

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        construction_exempt = module.rel.startswith(ALLOWED_PREFIX)
        enumeration_exempt = module.rel == POOL_MODULE
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            qual = module.resolve(dotted(node.func) or "")
            if not construction_exempt and qual.split(".")[-1] == TARGET:
                yield Finding(
                    checker=self.name, code="verifier-direct-construction",
                    message=(f"direct {TARGET}(...) construction outside "
                             "crypto/; submit through the resident verify "
                             "service (crypto/verify_service.py handle/"
                             "submit API) so dispatch stays coalesced and "
                             "priority-laned"),
                    path=module.rel, line=node.lineno, col=node.col_offset)
            elif not enumeration_exempt and qual in DEVICE_CALLS:
                yield Finding(
                    checker=self.name, code="verifier-device-enumeration",
                    message=(f"{qual}() outside crypto/device_pool.py; "
                             "the device pool owns inventory and group "
                             "layout — use device_pool.jax_devices() "
                             "or a DevicePool"),
                    path=module.rel, line=node.lineno, col=node.col_offset)
