"""HBM memory manager: a per-chip byte ledger arbitrating base weights,
adapter pool, paged-KV pool, and KV scales across deployments
(docs/MULTITENANT.md).

The serving plane historically assumed ONE deployment owns the chip — the
paged-KV pool sized itself against ``SCT_HBM_GB`` and nothing stopped a
second deployment from landing on the same device and OOMing mid-traffic.
This module replaces that assumption with admission-time reservation:
every :class:`~seldon_core_tpu.executor.generation.GenerativeModel`
registers its byte classes here at build, and with enforcement on
(``SCT_HBM_ENFORCE=1``) an over-committing build fails FAST with
:class:`HBMOverCommit` instead of an opaque device OOM at first traffic.

The ledger is deliberately host-side accounting (JAX owns the actual
allocations): its job is arbitration and attribution — the per-class byte
split joins the ``seldon_kv_bytes{class}`` gauges (PR 9) with
``class="adapter_pool"`` for the stacked multi-LoRA tensors, and the
snapshot rides ``GET /stats/breakdown``.
"""

from __future__ import annotations

import threading

from seldon_core_tpu.runtime import settings

#: byte classes the ledger recognises (free-form keys are allowed; these
#: are the ones the generative plane reports and the gauges label);
#: ``prefix_dram`` and ``suspend_dram`` live in the HOST ledger
#: (:func:`host_memory`), not the HBM one — demoted prefix KV and
#: preempted whole-slot suspend records (docs/PACKING.md) occupy host
#: DRAM, not chip memory
CLASSES = (
    "weights", "kv_pool", "kv_scales", "slot_state", "adapter_pool",
    "spec_heads", "draft_weights", "draft_kv",
    "prefix_dram", "suspend_dram",
)


class HBMOverCommit(RuntimeError):
    """An admission-time reservation would exceed the chip's HBM budget
    (only raised when enforcement is on)."""


class MemoryManager:
    """Byte ledger for one chip's HBM.

    ``budget_bytes`` defaults to ``SCT_HBM_GB`` (16 GiB — a v5e chip);
    ``enforce`` to ``SCT_HBM_ENFORCE`` (off by default so existing
    single-deployment setups and tests keep working — the ledger still
    tracks and reports, it just warns instead of raising).
    """

    def __init__(
        self,
        budget_bytes: int | None = None,
        *,
        enforce: bool | None = None,
    ):
        if budget_bytes is None:
            budget_bytes = int(settings.get_float("SCT_HBM_GB") * (1 << 30))
        if enforce is None:
            enforce = settings.get_bool("SCT_HBM_ENFORCE")
        self.budget_bytes = int(budget_bytes)
        self.enforce = bool(enforce)
        self._owners: dict[str, dict[str, int]] = {}
        self._lock = threading.Lock()
        self.rejections = 0

    # ------------------------------------------------------------- ledger

    def reserve(self, owner: str, classes: dict[str, int]) -> None:
        """Reserve ``owner``'s byte classes (replacing any prior
        reservation under the same key — a rebuild re-reserves, it never
        double-counts).  Raises :class:`HBMOverCommit` when enforcement is
        on and the total would exceed the budget; otherwise the
        over-commit is recorded and logged."""
        classes = {str(k): max(0, int(v)) for k, v in classes.items()}
        with self._lock:
            prior = sum(self._owners.get(owner, {}).values())
            total = self.reserved_bytes_locked() - prior + sum(classes.values())
            if total > self.budget_bytes:
                self.rejections += 1
                if self.enforce:
                    raise HBMOverCommit(
                        f"HBM reservation for {owner!r} "
                        f"({sum(classes.values())} bytes) would put the chip "
                        f"at {total} of {self.budget_bytes} budget bytes"
                    )
                import logging

                logging.getLogger(__name__).warning(
                    "HBM ledger over budget: %d of %d bytes after %r "
                    "(SCT_HBM_ENFORCE=1 makes this a build failure)",
                    total, self.budget_bytes, owner,
                )
            self._owners[owner] = classes

    def release(self, owner: str) -> None:
        with self._lock:
            self._owners.pop(owner, None)

    def reserved_bytes_locked(self) -> int:
        return sum(sum(c.values()) for c in self._owners.values())

    @property
    def reserved_bytes(self) -> int:
        with self._lock:
            return self.reserved_bytes_locked()

    def headroom_bytes(self) -> int:
        return max(0, self.budget_bytes - self.reserved_bytes)

    def by_class(self) -> dict[str, int]:
        with self._lock:
            out: dict[str, int] = {}
            for classes in self._owners.values():
                for k, v in classes.items():
                    out[k] = out.get(k, 0) + v
            return out

    def snapshot(self) -> dict:
        """The ledger for ``GET /stats/breakdown`` (generation section)."""
        with self._lock:
            by_class: dict[str, int] = {}
            for classes in self._owners.values():
                for k, v in classes.items():
                    by_class[k] = by_class.get(k, 0) + v
            reserved = sum(by_class.values())
            return {
                "budget_bytes": self.budget_bytes,
                "reserved_bytes": reserved,
                "headroom_bytes": max(0, self.budget_bytes - reserved),
                "enforce": self.enforce,
                "rejections": self.rejections,
                "by_class": by_class,
                "owners": {k: dict(v) for k, v in self._owners.items()},
            }


#: process-wide default ledger (one chip per engine process); tests build
#: their own with explicit budgets
MEMORY = MemoryManager()


_HOST_MEMORY: MemoryManager | None = None
_HOST_LOCK = threading.Lock()


def host_memory() -> MemoryManager:
    """Process-wide HOST-DRAM ledger, separate from the HBM one so the
    tiered prefix store's bytes (class ``prefix_dram``) and preemption
    suspend records (class ``suspend_dram``, docs/PACKING.md) never eat
    the chip budget or trip ``SCT_HBM_ENFORCE``.  Budget is the sum of
    the two host tiers' own budgets — ``SCT_PREFIX_DRAM_GB`` (0 GiB, the
    DRAM prefix tier is opt-in) and ``SCT_PACK_SUSPEND_GB`` (1 GiB, the
    per-deployment suspend-store bound); built lazily so tests that
    tweak the env vars before first touch see them."""
    global _HOST_MEMORY
    with _HOST_LOCK:
        if _HOST_MEMORY is None:
            budget = int(
                (
                    settings.get_float("SCT_PREFIX_DRAM_GB")
                    + settings.get_float("SCT_PACK_SUSPEND_GB")
                )
                * (1 << 30)
            )
            _HOST_MEMORY = MemoryManager(budget, enforce=False)
        return _HOST_MEMORY
