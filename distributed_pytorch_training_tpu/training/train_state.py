"""TrainState — the pytree that replaces the reference's mutable
(model, optimizer, scaler) triple (/root/reference/train_ddp.py:335-346).

Functional: every train step maps state -> state. No GradScaler field exists
because bf16 needs no loss scaling (fp32-range exponent; SURVEY.md §2b row 4).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import optax


@flax.struct.dataclass
class TrainState:
    step: jax.Array  # int32 scalar
    params: Any
    batch_stats: Any  # BatchNorm EMAs ({} for stat-free models)
    opt_state: Any
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    # Explicit-reducer side state (parallel/grad_sync.py): error-feedback
    # residuals for the int8 gradient wire ({"ef": ...}, per-replica rows
    # sharded over the batch axes — keyed per bucket/leaf for the bucketed
    # and zero1 scatters, per LAYER GROUP name for fsdp_explicit's
    # per-layer scatter). {} (no leaves) for every other mode — the
    # pytree/checkpoint shape is unchanged unless int8 is engaged.
    grad_sync: Any = dataclasses.field(default_factory=dict)

    @classmethod
    def create(cls, apply_fn: Callable, params: Any, tx: optax.GradientTransformation,
               batch_stats: Any = None, opt_state: Any = None) -> "TrainState":
        """``opt_state`` overrides the default ``tx.init(params)`` — the
        ZeRO-1 path (training/loop.py) constructs its optimizer state in the
        flat-padded-sharded layout (optim.zero1_opt_state), where every
        moment leaf is a 1-D chunk of the flattened parameter partitioned
        across the data-parallel replicas rather than a replicated copy.
        Checkpointing is layout-agnostic either way: orbax restores into
        whatever sharded template the run constructs (checkpoint.py)."""
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats if batch_stats is not None else {},
            opt_state=tx.init(params) if opt_state is None else opt_state,
            apply_fn=apply_fn,
            tx=tx,
        )

    def apply_gradients(self, grads: Any, batch_stats: Any = None) -> "TrainState":
        """optimizer.step() equivalent (ref :214 / :208)."""
        with jax.named_scope("optimizer"):
            updates, new_opt_state = self.tx.update(
                grads, self.opt_state, self.params)
            new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=self.batch_stats if batch_stats is None else batch_stats,
        )

    def param_count(self) -> int:
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(self.params))
