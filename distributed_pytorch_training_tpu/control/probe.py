"""Capacity probes: real feeds behind ``CapacityWatch(probe=...)``.

`resilience.capacity.CapacityWatch` has carried an optional ``probe``
hook since ISSUE 12 — a zero-arg callable returning the fleet's current
replica capacity — but until now nothing real was plugged into it. Two
feeds live here:

* :func:`heartbeat_capacity_probe` — capacity read off a list of TCP
  ports (`resilience.heartbeat.registry_snapshot`): each listed port
  vouches for an equal share of the fleet, so ``total * up_ports //
  n_ports``.
* :class:`FileCapacityFeed` — the documented interface stub for
  EXTERNAL feeds (GKE node-pool state, GCE preemption notices): any
  zero-arg callable returning an int is a valid probe, and the file
  form is the smallest adapter — an agent writes the current replica
  count to a path, the watch polls it. A feed that raises or hangs is
  legitimate steady-state behavior for an external endpoint; the watch
  CONTAINS it (degrades to the last committed reading with a loud
  ``capacity_probe_errors`` event — see ``CapacityWatch.available``),
  so feed authors do not need their own retry shell.

Probes return TOTAL capacity (how many replicas could run now), not a
delta; the watch clamps to ``[0, total]`` and commits via its own
lose/sync/restore bookkeeping.
"""

from __future__ import annotations

import os
from typing import Callable, Sequence

from ..resilience import heartbeat


def heartbeat_capacity_probe(total: int, ports: Sequence[int],
                             timeout: float = 0.2) -> Callable[[], int]:
    """A probe reading capacity off the liveness of ``ports``.

    ``total`` is the full-fleet replica count the watch was built with;
    each listed port vouches for an equal share, so 2 of 3 ports up on an
    8-replica fleet reads as ``8 * 2 // 3 = 5``. With every port dark the
    probe reads 0 — the watch's clamp and grow-threshold logic decide
    what to do with it.
    """
    if total < 0:
        raise ValueError("total capacity must be >= 0")
    plist = list(ports)

    def probe() -> int:
        if not plist:
            return total  # nothing listed: no evidence of loss
        snapshot = heartbeat.registry_snapshot(plist, timeout=timeout)
        up = sum(1 for alive in snapshot.values() if alive)
        return (total * up) // len(plist)

    return probe


class FileCapacityFeed:
    """External-feed adapter: read the current replica capacity from a
    file an outside agent maintains (GKE/GCE preemption watchers,
    cluster schedulers). The file holds one integer; a missing file,
    unreadable content, or a hung filesystem raises — and that is FINE:
    ``CapacityWatch.available`` contains probe failures by design
    (last-known reading + a ``capacity_probe_errors`` counter event),
    so this adapter stays a dumb read with no retry logic of its own."""

    def __init__(self, path: str):
        self.path = str(path)

    def __call__(self) -> int:
        with open(self.path, "r", encoding="utf-8") as fh:
            return int(fh.read().strip())

    def write(self, capacity: int) -> None:
        """Test/demo helper: atomically publish a reading the way a real
        agent should (write-then-rename, so the feed never reads a torn
        value)."""
        tmp = f"{self.path}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            fh.write(f"{int(capacity)}\n")
        os.replace(tmp, self.path)
