"""Two-process multi-host runtime test (VERDICT r2 #9: the rendezvous
branches, host collectives, and multi-process shard_batch had no live test).

Spawns 2 real OS processes on the CPU backend, 2 virtual devices each — the
smallest honest model of a 2-host pod. They rendezvous through
``jax.distributed.initialize`` via the ``DPT_*`` env contract
(runtime/dist.py), mirroring the reference's torchrun ``env://`` rendezvous
(/root/reference/train_ddp.py:53-68). The worker (tests/_multihost_worker.py)
asserts the whole surface: DistContext topology, barrier,
broadcast_from_main, reduce_scalar, host_all_gather, per-process seed rule,
multi-host shard_batch, and a 4-step sharded training run whose loss
decreases and agrees bit-for-bit across processes.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

WORKER = Path(__file__).parent / "_multihost_worker.py"
REPO = Path(__file__).parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_rendezvous_and_training():
    """Two real processes rendezvous through ``jax.distributed`` on the
    CPU backend, run host collectives and a training step across both, and
    converge to the same loss."""
    # bounded by the workers' communicate(timeout=240) below
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "DPT_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "DPT_NUM_PROCESSES": "2",
            "DPT_PROCESS_ID": str(rank),
        })
        # a worker must not inherit the parent test's single-process state
        env.pop("JAX_NUM_CPU_DEVICES", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(WORKER)], env=env, cwd=str(REPO),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))

    outs = []
    try:
        for rank, p in enumerate(procs):
            out, err = p.communicate(timeout=240)
            outs.append((rank, p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    for rank, rc, out, err in outs:
        assert rc == 0, (
            f"worker {rank} failed rc={rc}\nstdout:\n{out}\nstderr:\n{err}")
        assert f"WORKER_OK rank={rank}" in out, out

    # both ranks converged to the same loss (printed value matches)
    import re
    losses = {re.search(r"loss=([0-9.]+)", out).group(1)
              for _, _, out, _ in outs}
    assert len(losses) == 1, f"ranks diverged: {losses}"
