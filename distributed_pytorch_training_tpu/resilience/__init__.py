"""resilience/ — fault-tolerant training: fault injection,
checkpoint-restart supervision, elasticity.

The reference has no failure story (SURVEY.md §5: a crashed rank hangs the
NCCL job). This subsystem turns "a fault happened" into "the run finished
anyway":

* :mod:`.heartbeat` — ``hard_exit`` (the one sanctioned ``os._exit``) and
  the TCP port-list liveness sample the capacity probe reads.
* :mod:`.faults` — deterministic fault injection (``FaultPlan`` /
  ``FaultInjector``): ``crash@step=7``, ``sigterm@step=12``,
  ``torn_ckpt@save=2``, ``loader_stall@step=5:2.5s``. Hooks thread through
  ``training/loop.py``, the checkpoint save path, and ``data/loader.py``,
  and are plain ``None`` when no plan is armed — the hot path is untouched.
* :mod:`.supervisor` — the in-process restart supervisor wrapping the
  epoch loop: on a step/save failure it restores the latest *valid*
  checkpoint (``training/checkpoint.py`` manifest verification skips torn
  ones), replays behind a step fence (no optimizer step double-applies;
  same-seed data order via the deterministic sampler + ``state.step``-folded
  RNG + restored EF residuals) and retries under a bounded
  exponential-backoff-with-jitter ``RetryPolicy``, draining preemptions
  gracefully instead of racing them.

Elasticity is BIDIRECTIONAL (ISSUE 11 shrank, ISSUE 12 grows and crosses
process boundaries):

* :mod:`.elastic` — the N↔M reshard orchestration (``plan_elastic_world``,
  ``reshard_train_state``, the raw cross-process variant
  ``reshard_raw_state``);
* :mod:`.capacity` — the grow side: a pollable ``CapacityWatch``
  registry the ``capacity_return@step=k`` chaos fault
  (or a real cluster probe) feeds, polled by the Supervisor at segment
  boundaries to re-plan UP when preempted capacity returns;
* :mod:`.fleet` — the cross-PROCESS orchestrator: launches ``train.py``
  children, watches exit codes, and relaunches with a *different* world
  size over the shared checkpoint directory (``resilience fleet``).

``python -m distributed_pytorch_training_tpu.resilience chaos`` (also the
``resilience`` console script) runs a scripted fault schedule against a
short CPU-mesh training run and reports recovery stats — the demo and the
test harness in one; ``resilience fleet`` runs the subprocess-relaunch
scenario end to end.
"""

from .capacity import CapacityWatch  # noqa: F401
from .faults import FaultError, FaultInjector, FaultPlan  # noqa: F401
from .supervisor import RetryPolicy, RunReport, Supervisor  # noqa: F401
