"""Capacity watcher: the grow side of elasticity (ISSUE 12).

A replica death shrinks the run (resilience/elastic.py); nothing in the
stack noticed capacity COMING BACK: a run that shrank 8 -> 4 after a
preemption stayed shrunk forever, paying double per-device batch (and the
matching step-time) long after the preempted chips returned. The
:class:`CapacityWatch` closes that half:

* it is a REGISTRY — ``total`` replicas exist in the fleet, ``available``
  of them are currently usable. Replica deaths call :meth:`lose`,
  capacity returns call :meth:`restore` (the chaos injector's
  ``capacity_return@step=k`` fault drives it deterministically; a real
  deployment points ``probe`` at its device/cluster feed);
* it is POLLED, never raced: the Supervisor asks :meth:`poll_grow` at
  SEGMENT BOUNDARIES only — after the segment drained and its checkpoint
  was written — so a grow is always anchored at a durable, labeled
  coordinate (the same discipline as the preemption drain). A mid-step
  capacity blip can never tear a step;
* growing is a RE-PLAN, not a guess: the Supervisor hands the available
  count to its ``replan_cb``, which picks the largest feasible world
  ``<= available`` dividing the FIXED global batch
  (:func:`.elastic.plan_elastic_world`) — capacity that returns in a
  quantity no feasible world can use (5 survivors, batch 16) changes
  nothing.

Thread-safe: the injector's step fence (main thread), a probe thread, and
the Supervisor's boundary poll may all touch the counts.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

from ..telemetry import recorder as _telemetry
from ..utils.locktrace import named_lock


class CapacityWatch:
    """Pollable fleet-capacity registry.

    ``total`` is the full fleet size (replicas). ``available`` starts at
    ``total`` unless given. ``probe`` (optional) is a zero-arg callable
    returning the CURRENT available count from an external source — when
    set, it is consulted (and the internal count synced to it) on every
    :meth:`available` read; ``lose``/``restore`` still work as manual
    overrides between probes (the chaos harness path).

    Probe failures are CONTAINED (ISSUE 20 satellite): a probe that
    raises — or, with ``probe_timeout_s`` set, hangs past the budget —
    degrades that read to the last committed count and emits a loud
    ``capacity_probe_errors`` counter event; it never escapes into the
    Supervisor's boundary poll or grow path. An external feed (GKE/GCE
    preemption watchers, control/probe.py ``FileCapacityFeed``) WILL
    have bad days, and a flaky feed must cost staleness, not the run.
    """

    def __init__(self, total: int, available: Optional[int] = None,
                 probe: Optional[Callable[[], int]] = None,
                 probe_timeout_s: Optional[float] = None):
        if total < 1:
            raise ValueError(f"a fleet needs >= 1 replica, got {total}")
        if probe_timeout_s is not None and probe_timeout_s <= 0:
            raise ValueError("probe_timeout_s must be positive "
                             f"(got {probe_timeout_s})")
        self.total = int(total)
        self._available = int(total if available is None else available)  # guarded-by: _lock
        if not 0 <= self._available <= self.total:
            raise ValueError(
                f"available ({self._available}) must lie in "
                f"[0, total={self.total}]")
        self._probe = probe   # set once here, immutable after
        # hang containment: with a timeout set, probe calls ride ONE
        # lazily-started daemon worker (`_ProbeWorker`) and a call
        # overrunning the budget degrades like a raise. None = direct
        # call (zero threads — the autopilot-off pin); the worker only
        # ever exists when BOTH a probe and a timeout are armed.
        self._probe_timeout_s = probe_timeout_s
        self._probe_worker: Optional[_ProbeWorker] = None  # guarded-by: _worker_lock
        self._worker_lock = threading.Lock()
        self._lock = named_lock("CapacityWatch._lock")
        # set whenever capacity INCREASES (restore / a probe reading above
        # the last one) — a cheap "worth polling" hint for callers that
        # want to wait instead of poll; cleared by poll_grow
        self.returned = threading.Event()

    def _consult_probe(self) -> Optional[int]:
        """One contained probe read: the clamped fresh count, or None
        when the probe raised/hung (degrade to last-known)."""
        try:
            if self._probe_timeout_s is None:
                raw = self._probe()
            else:
                with self._worker_lock:
                    if self._probe_worker is None:
                        self._probe_worker = _ProbeWorker(self._probe)
                    worker = self._probe_worker
                raw = worker.call(self._probe_timeout_s)
            return max(0, min(int(raw), self.total))
        except Exception as e:  # noqa: BLE001 — ANY probe failure is a
            # degraded reading, never a poll/grow-path error
            _telemetry.counter(
                "capacity_probe_errors", 1, error=type(e).__name__,
                detail=str(e)[:200])
            return None

    def available(self) -> int:
        """Current available replica count (probe-synced when armed;
        probe failures degrade to the last committed reading)."""
        # consult the probe OUTSIDE the lock: it is an arbitrary external
        # callable (a device/cluster feed — possibly a network round
        # trip, possibly re-entering this registry), and holding the
        # lock across it would serialize every lose/restore/sync on the
        # slowest probe — and self-deadlock on a re-entrant one
        fresh: Optional[int] = None
        if self._probe is not None:
            fresh = self._consult_probe()
        with self._lock:
            if fresh is not None:
                if fresh > self._available:
                    self.returned.set()
                self._available = fresh
            return self._available

    def lose(self, n: int = 1) -> int:
        """``n`` replicas left the fleet (a replica death); returns the
        new available count (never below 0)."""
        with self._lock:
            self._available = max(0, self._available - int(n))
            return self._available

    def sync(self, available: int) -> int:
        """Set the available count ABSOLUTELY (clamped to [0, total]) —
        the Supervisor's death-restart bookkeeping: a replica death
        re-plans over the SURVIVING ACTIVE replicas (``old_world - 1``),
        and the registry must agree with that decision or the next
        boundary poll would see phantom idle capacity and grow right back
        mid-incident. Capacity genuinely returning is :meth:`restore`
        (the ``capacity_return`` fault / a probe reading)."""
        with self._lock:
            self._available = max(0, min(int(available), self.total))
            return self._available

    def restore(self, n: Optional[int] = None) -> int:
        """``n`` replicas came back (``None`` = all of them: available
        returns to ``total``); returns the new available count."""
        with self._lock:
            if n is None:
                self._available = self.total
            else:
                self._available = min(self.total,
                                      self._available + int(n))
            self.returned.set()
            return self._available

    def poll_grow(self, current_world: Optional[int]) -> Optional[int]:
        """The Supervisor's segment-boundary poll: the available count
        when it EXCEEDS ``current_world`` (a grow may be feasible — the
        replan decides whether a larger world actually divides the global
        batch), else None. Emits a ``capacity_watch`` telemetry span so
        the summary's step-time split accounts the polling, and clears
        :attr:`returned`."""
        with _telemetry.span("capacity_watch", world=current_world):
            avail = self.available()
            # the /metrics capacity gauge: every boundary poll publishes
            # what the fleet registry currently believes is available
            _telemetry.gauge("capacity_available", avail)
            self.returned.clear()
            if current_world is None or avail <= current_world:
                return None
            return avail


class _ProbeWorker:
    """One daemon thread boxing a possibly-hanging probe callable.

    ``call(timeout)`` submits a request and waits at most ``timeout``
    seconds; an overrun raises TimeoutError to the caller while the
    worker keeps running the hung call. The next ``call`` first tries to
    reap that stale result (the probe recovered: discard the old answer,
    submit fresh); while the old call is STILL in flight it fails fast
    with TimeoutError instead of queueing behind a wedged feed — every
    path out of here is a contained degrade in
    ``CapacityWatch._consult_probe``, never a stuck boundary poll."""

    def __init__(self, fn: Callable[[], int]):
        import queue

        self._fn = fn
        self._req: "queue.Queue" = queue.Queue()
        self._res: "queue.Queue" = queue.Queue()
        self._in_flight = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="dpt-capacity-probe")
        self._thread.start()

    def _run(self) -> None:
        while True:
            self._req.get()
            try:
                result = ("ok", self._fn())
            except BaseException as e:  # noqa: BLE001 — relayed verbatim
                result = ("err", e)
            self._res.put(result)

    def call(self, timeout: float) -> int:
        import queue

        if self._in_flight.is_set():
            # a previous call overran its budget; reap it if it finished
            try:
                self._res.get_nowait()
                self._in_flight.clear()   # recovered — stale answer dropped
            except queue.Empty:
                raise TimeoutError(
                    "capacity probe still hung from a previous poll")
        self._in_flight.set()
        self._req.put(None)
        try:
            tag, value = self._res.get(timeout=timeout)
        except queue.Empty:
            # leave _in_flight set: the worker is still inside the probe
            raise TimeoutError(
                f"capacity probe exceeded its {timeout:g}s budget")
        self._in_flight.clear()
        if tag == "err":
            raise value
        return value
