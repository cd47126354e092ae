"""Preemption-aware training — the failure-recovery story.

The reference has none (SURVEY.md §5 "Failure detection/elastic recovery:
Absent — a crashed rank hangs the NCCL job"). TPU pods are preemptible, so
the minimum useful story is: catch the preemption signal (SIGTERM), finish
the in-flight step, write a checkpoint, exit 0; the relaunched job resumes
from it (`--resume`). That turns a preemption from "lose the run" into "lose
at most one epoch slice".

No elastic re-sizing: XLA SPMD programs are compiled for a fixed mesh, so the
honest TPU design is checkpoint-restart at the same (or re-specified)
topology rather than DDP-style dynamic world resizing.
"""

from __future__ import annotations

import os
import signal
import threading
from typing import Optional

from ..resilience.heartbeat import hard_exit
from ..utils.logging import log_main

# Hard deadline for the graceful path. "Stop at the next epoch boundary"
# assumes the process is making progress; a SIGTERM that lands mid-compile
# (minutes) or while the backend is wedged (forever) must still kill the
# process — a zombie that swallowed SIGTERM keeps its device claim and
# blocks every subsequent job from acquiring the chip.
_GRACE_ENV = "DPT_PREEMPT_GRACE_SECONDS"
_GRACE_DEFAULT = 600.0


class PreemptionGuard:
    """Installs SIGTERM/SIGINT handlers that request a graceful stop.

    Usage::

        guard = PreemptionGuard.install()
        for epoch in range(...):
            train_epoch(...)
            if guard.should_stop:
                ckpt.save(epoch + 1, state, wait=True)
                break
        guard.disarm()  # graceful path completed; cancel the deadline

    Handlers chain to any previously-installed handler; `should_stop` is a
    plain flag so the hot loop pays nothing for it. Signals received twice
    fall through to the previous handler (second Ctrl-C still kills). The
    first signal also arms a hard deadline (``DPT_PREEMPT_GRACE_SECONDS``,
    default 600): if the process hasn't exited — or called ``disarm()`` —
    by then, it force-exits with status 143 rather than linger as a
    device-holding zombie.
    """

    _installed: Optional["PreemptionGuard"] = None

    def __init__(self):
        self._stop = threading.Event()
        self._prev = {}
        self._deadline: Optional[threading.Timer] = None
        # test seam: replaced to observe the force-exit without dying.
        # hard_exit is resilience/heartbeat.py's sanctioned abrupt exit
        # (the no-bare-os-exit analysis rule bans raw os._exit here): a
        # zombie that swallowed SIGTERM keeps its device claim, so the
        # deadline expiry is one of the two legitimate abrupt-exit cases.
        self._force_exit = lambda: hard_exit(143)

    @property
    def should_stop(self) -> bool:
        return self._stop.is_set()

    def request_stop(self) -> None:
        self._stop.set()

    def _handler(self, signum, frame):
        if self._stop.is_set():
            # second signal: defer to the previous behavior (hard exit)
            prev = self._prev.get(signum)
            if callable(prev):
                prev(signum, frame)
            else:
                signal.signal(signum, prev or signal.SIG_DFL)
                signal.raise_signal(signum)
            return
        # never raise inside a signal handler: a malformed env value must
        # not turn SIGTERM into a crash-without-checkpoint
        try:
            grace = float(os.environ.get(_GRACE_ENV, _GRACE_DEFAULT))
        except (TypeError, ValueError):
            grace = _GRACE_DEFAULT
        log_main(f"Received signal {signum}: will checkpoint and stop at the "
                 f"next epoch boundary (hard exit in {grace:.0f}s if the "
                 "graceful path stalls)")
        self._stop.set()
        self._arm_deadline(grace)

    def _arm_deadline(self, grace: float) -> None:
        def expire():
            log_main(f"Graceful stop did not complete within {grace:.0f}s "
                     "of the signal; force-exiting (143)")
            self._force_exit()

        self._deadline = threading.Timer(grace, expire)
        self._deadline.daemon = True
        self._deadline.start()

    def disarm(self) -> None:
        """Cancel the hard-exit deadline — the graceful path completed (or
        the caller, e.g. a notebook, keeps the process for another run)."""
        if self._deadline is not None:
            self._deadline.cancel()
            self._deadline = None

    def reset(self) -> None:
        """Disarm a previously-set stop flag (a new run starts fresh)."""
        self._stop.clear()
        self.disarm()

    @classmethod
    def install(cls, reset: bool = True) -> "PreemptionGuard":
        """Idempotent: repeated calls return the same guard. By default the
        stale stop flag from a previous run in this process is cleared —
        otherwise a sweep/notebook calling main() twice would silently stop
        run 2 after one epoch because run 1 was preempted."""
        if cls._installed is not None:
            if reset:
                cls._installed.reset()
            return cls._installed
        guard = cls()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                guard._prev[sig] = signal.signal(sig, guard._handler)
            except (ValueError, OSError):
                # non-main thread or restricted env: degrade to manual
                # request_stop(); training still works
                pass
        cls._installed = guard
        return guard
