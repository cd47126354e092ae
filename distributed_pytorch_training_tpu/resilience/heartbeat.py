"""The sanctioned abrupt exit, and TCP port liveness.

``hard_exit`` is the one place ``os._exit`` may be called (the
``no-bare-os-exit`` analysis rule flags every other site);
``port_listening`` / ``registry_snapshot`` are the port-list liveness
sample the control plane's capacity probe reads (control/probe.py).

This module never imports jax: linting and orchestrators that must not
initialize a backend import it.
"""

from __future__ import annotations

import os
import socket
from typing import Sequence


def port_listening(port: int, timeout: float = 0.2) -> bool:
    """TCP connect probe of one local port."""
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=timeout):
            return True
    except OSError:
        return False


def registry_snapshot(ports: Sequence[int], timeout: float = 0.2) -> dict:
    """One liveness sample of a port list: ``{port: up}``.

    The control plane's capacity probe
    (control/probe.py ``heartbeat_capacity_probe``) reads fleet capacity
    off this snapshot — each listed port vouches for an equal share of
    the fleet — and the autopilot's decision evidence embeds it, so an
    eviction/grow decision records WHICH port was dark when it was
    taken."""
    return {int(p): port_listening(int(p), timeout=timeout) for p in ports}


def hard_exit(code: int) -> None:
    """The ONE sanctioned abrupt process exit (``os._exit``).

    An abrupt exit is legitimate only when a zombie would keep a device
    claim past a deadline the process already missed (preemption's hard
    deadline) — and even then the caller must have already attempted or
    bounded any cleanup it owes. Everywhere else, ``os._exit`` while
    holding the chip skips the runtime's release of it — the
    ``no-bare-os-exit`` analysis rule flags any other call site."""
    os._exit(code)
