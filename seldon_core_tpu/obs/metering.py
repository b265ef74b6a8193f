"""Per-tenant usage metering — who spent the device (docs/OBSERVABILITY.md).

The serving plane time-shares one chip across co-resident deployments
(the PR 12 arbiter), thousands of LoRA tenants (PR 10), and elastic
pools (PR 16), but the metrics stop at per-deployment request counters:
nobody can answer "which tenant spent the device" or "what did that shed
request cost".  The :class:`UsageMeter` is the missing ledger — a
process-wide table of cumulative usage counters keyed by
``(deployment, adapter, qos_class)``:

* **device seconds** — the seconds each fused decode block occupied the
  device (the scheduler's device ledger, obs/device.py) are split across
  the slots it served *by token share* (a slot
  that emitted 3 of the block's 12 tokens is charged 25% of the block);
  batcher (non-generative) steps charge their whole measured device time
  to the owning deployment;
* **arbiter grant seconds** — wall time a deployment actually held the
  device grant, straight from the arbiter's holder transitions;
* **tokens** — prefilled, decoded, speculative-accepted, and prefix-tier
  tokens *saved* per tier (hbm/dram/peer: reuse someone already paid
  for);
* **costs of failure** — shed and reaped request counts plus the decode
  tokens already burned on requests that were later reaped
  (``tokens_wasted``), and suspend byte-seconds parked in the host
  suspend store.

Strict no-host-sync rule (same contract as the timeline ledger): every
``add`` is made from values the host ALREADY holds at a fused-block sync
point — fetched token counts, grant timestamps, reservation bookkeeping.
Nothing here touches a device array, so the ≤1-sync-per-fused-block
audit (tests/test_perf.py) runs with metering on.

Memory is bounded by construction: at most ``SCT_METER_MAX_KEYS`` live
key rows (LRU; evictions fold counter-exactly into an ``other`` rollup
row, so totals are conserved), and the ``/prometheus`` export surfaces
only the top ``SCT_METER_TOP_K`` rows by attributed device time plus the
``other`` rollup — label cardinality stays flat no matter how many
tenants pass through.  ``snapshot()`` is all-numeric-leaves by design so
the fleet collector's counter merge (obs/fleet.py ``_merge_numeric``)
sums per-replica tables counter-exactly into ``/stats/fleet``.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from seldon_core_tpu.runtime import settings

ENABLE_ENV = "SCT_METER"
MAX_KEYS_ENV = "SCT_METER_MAX_KEYS"
TOP_K_ENV = "SCT_METER_TOP_K"

# the fixed counter vocabulary; every row is {field: float} over these.
# Additions here show up in /stats/usage, the fleet merge, and the
# seldon_usage_* export without further plumbing.
FIELDS = (
    "device_s",            # token-share-attributed seconds of device occupancy
    "grant_s",             # arbiter grant-interval wall seconds
    "tokens_prefill",      # prompt tokens actually prefilled on device
    "tokens_decode",       # tokens emitted by fused decode blocks
    "tokens_spec_accepted",  # of those, accepted speculative drafts
    # per-proposer split of tokens_spec_accepted (PR 20: ngram history
    # ring / fused Medusa-style heads / co-resident draft model) — keeps
    # cost attribution honest when deployments mix speculation methods
    "tokens_spec_accepted_ngram",
    "tokens_spec_accepted_heads",
    "tokens_spec_accepted_draft",
    "tokens_saved_hbm",    # prefix tokens NOT prefilled: HBM-resident hit
    "tokens_saved_dram",   # ... promoted from the host-DRAM tier
    "tokens_saved_peer",   # ... pulled from a peer replica
    "tokens_wasted",       # decode tokens burned on later-reaped requests
    "requests_completed",
    "requests_shed",       # QoS admission / queue-overflow sheds
    "requests_reaped",     # deadline reaps + client disconnects
    "requests_cached",     # answered from the response cache (zero device)
    "suspend_byte_s",      # bytes x seconds parked in the suspend store
)

OTHER_KEY = ("other", "", "")

_SEP = "|"


def key_str(deployment: str, adapter: str = "", qos: str = "") -> str:
    """The wire form of a meter key: ``deployment|adapter|qos``.  The
    null adapter is the empty string — base-deployment usage keeps its
    own row rather than vanishing into a synthetic tenant."""
    return f"{deployment}{_SEP}{adapter}{_SEP}{qos}"


def split_key(key: str) -> tuple[str, str, str]:
    parts = key.split(_SEP, 2)
    while len(parts) < 3:
        parts.append("")
    return parts[0], parts[1], parts[2]


class UsageMeter:
    """Bounded per-tenant usage counter table (thread-safe)."""

    def __init__(
        self,
        max_keys: int | None = None,
        top_k: int | None = None,
        enabled: bool | None = None,
    ):
        if max_keys is None:
            max_keys = settings.get_int(MAX_KEYS_ENV)
        if top_k is None:
            top_k = settings.get_int(TOP_K_ENV)
        if enabled is None:
            enabled = settings.get_bool(ENABLE_ENV)
        self.enabled = bool(enabled)
        self.max_keys = max(1, int(max_keys))
        self.top_k = max(1, int(top_k))
        self._lock = threading.Lock()
        # LRU key table: key string -> {field: float}.  Bounded: evictions
        # fold into _other, never dropped (conservation over cardinality).
        self._table: OrderedDict[str, dict] = OrderedDict()
        self._other: dict[str, float] = {}
        self.evicted = 0

    # -- recording -----------------------------------------------------------

    def add(
        self, deployment: str, adapter: str = "", qos: str = "", **fields: float
    ) -> None:
        """Fold ``fields`` (from :data:`FIELDS`) into the row for
        ``(deployment, adapter, qos)``.  O(1) under one lock; called only
        at fused-block sync points, never per token."""
        if not self.enabled or not fields:
            return
        k = key_str(deployment, adapter, qos)
        with self._lock:
            row = self._table.get(k)
            if row is None:
                row = {}
                self._table[k] = row
                if len(self._table) > self.max_keys:
                    _, old = self._table.popitem(last=False)
                    for f, v in old.items():
                        self._other[f] = self._other.get(f, 0.0) + v
                    self.evicted += 1
            else:
                self._table.move_to_end(k)
            for f, v in fields.items():
                row[f] = row.get(f, 0.0) + v

    def reset(self) -> None:
        with self._lock:
            self._table.clear()
            self._other.clear()
            self.evicted = 0

    # -- read side -----------------------------------------------------------

    def size(self) -> int:
        with self._lock:
            return len(self._table)

    def totals(self) -> dict[str, float]:
        """Every field summed across all rows + the rollup (conserved
        across LRU evictions by construction)."""
        with self._lock:
            out = dict(self._other)
            for row in self._table.values():
                for f, v in row.items():
                    out[f] = out.get(f, 0.0) + v
        return out

    def snapshot(self) -> dict:
        """The ``GET /stats/usage`` payload.  All non-bool leaves are
        numeric counters so the fleet collector merges replica snapshots
        counter-exactly (sums equal the union)."""
        with self._lock:
            keys = {k: dict(row) for k, row in self._table.items()}
            other = dict(self._other)
            evicted = self.evicted
        totals: dict[str, float] = dict(other)
        for row in keys.values():
            for f, v in row.items():
                totals[f] = totals.get(f, 0.0) + v
        return {
            "enabled": self.enabled,
            "keys": keys,
            "other": other,
            "evicted": evicted,
            "total": totals,
        }

    def export_rows(self) -> list[tuple[tuple[str, str, str], dict]]:
        """Rows for the ``seldon_usage_*`` gauge export: the top
        ``top_k`` keys by attributed device time (grant time breaking
        ties), everything else — including LRU-evicted history — summed
        into one ``other`` row.  Bounded label cardinality by design."""
        with self._lock:
            rows = [(k, dict(row)) for k, row in self._table.items()]
            other = dict(self._other)
        rows.sort(
            key=lambda kr: (
                kr[1].get("device_s", 0.0),
                kr[1].get("grant_s", 0.0),
                kr[1].get("tokens_decode", 0.0) + kr[1].get("tokens_prefill", 0.0),
            ),
            reverse=True,
        )
        out = [(split_key(k), row) for k, row in rows[: self.top_k]]
        for _, row in rows[self.top_k:]:
            for f, v in row.items():
                other[f] = other.get(f, 0.0) + v
        if other:
            out.append((OTHER_KEY, other))
        return out


# default process-wide meter (mirrors obs.timeline.TIMELINE)
METER = UsageMeter()


def get_meter() -> UsageMeter:
    return METER
