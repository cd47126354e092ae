"""Tasks: what a batch means and how loss/metrics are computed.

The reference hardcodes one task — image classification with
CrossEntropyLoss and top-1 accuracy (/root/reference/train_ddp.py:217-222,
:338). Here the task is a pluggable object so the same Trainer drives the
vision configs and the BERT/GPT-2 language configs (BASELINE.json:6-12).

Contract: ``loss_and_metrics`` returns ``(loss, (metrics, new_batch_stats))``
where metrics are *weighted sums* (not means) so they accumulate across steps
and reduce across hosts exactly like the reference's sample-weighted sums
(ref :217-222, :246-253):
  - "loss_sum":  sum(per_sample_loss * weight)
  - "correct":   sum(is_correct * weight)   (task-defined notion of correct)
  - "weight":    sum(weight)
All three stay on device until a print boundary (avoiding the reference's
per-step ``.item()`` sync anti-pattern, ref :217/:220; SURVEY.md §3.2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

from ..data.augment import normalize_images, random_crop_flip
from ..parallel.collectives import TpShardedLogits, tp_parallel_cross_entropy

Metrics = Dict[str, jnp.ndarray]


class Task:
    """Interface; see module docstring for the metrics contract."""

    def loss_and_metrics(
        self,
        state,
        params,
        batch: Dict[str, jnp.ndarray],
        rng: jax.Array,
        train: bool,
    ) -> Tuple[jnp.ndarray, Tuple[Metrics, Any]]:
        raise NotImplementedError


@dataclasses.dataclass
class ImageClassificationTask(Task):
    """CIFAR/ImageNet classification (ref :217-222, :338).

    Augmentation (RandomCrop+Flip, ref :91-96) and normalization (ref :86-89)
    run on device as part of the compiled step — uint8 in, logits out.
    """

    mean: Sequence[float]
    std: Sequence[float]
    augment: bool = True
    crop_padding: int = 4
    compute_dtype: Any = jnp.float32

    def loss_and_metrics(self, state, params, batch, rng, train):
        images = batch["image"]
        if train and self.augment:
            images = random_crop_flip(images, rng, padding=self.crop_padding)
        x = normalize_images(images, self.mean, self.std, dtype=self.compute_dtype)

        variables = {"params": params}
        has_stats = bool(jax.tree_util.tree_leaves(state.batch_stats))
        if has_stats:
            variables["batch_stats"] = state.batch_stats

        if train and has_stats:
            logits, mutated = state.apply_fn(
                variables, x, train=True, mutable=["batch_stats"])
            new_stats = mutated["batch_stats"]
        else:
            logits = state.apply_fn(variables, x, train=train)
            new_stats = state.batch_stats

        labels = batch["label"]
        w = batch["weight"]
        per_sample = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels)
        weight_sum = w.sum()
        loss = (per_sample * w).sum() / jnp.maximum(weight_sum, 1.0)

        correct = ((jnp.argmax(logits, axis=-1) == labels) * w).sum()
        metrics = {
            "loss_sum": (per_sample * w).sum(),
            "correct": correct,
            "weight": weight_sum,
        }
        return loss, (metrics, new_stats)


@dataclasses.dataclass
class LanguageModelingTask(Task):
    """Causal next-token prediction (the GPT-2 355M config, BASELINE.json:12).

    Batch: {"input_ids": (B, S) int32, "weight": (B,)}. Loss = CE of token
    t+1 given tokens <=t, averaged over real (weighted) positions, plus
    `aux_loss_weight` x any auxiliary losses the model sows into its
    ``"losses"`` collection (0 for dense models — sowing is a no-op there).
    "correct" is next-token top-1 — so summarize() reports token accuracy.
    """

    compute_dtype: Any = jnp.float32
    aux_loss_weight: float = 0.0

    def loss_and_metrics(self, state, params, batch, rng, train):
        ids = batch["input_ids"]
        # Thread the step rng into apply so stochastic model internals
        # (dropout, MoE router jitter — models/moe.py router_noise) have the
        # "dropout" stream available at train time.
        rngs = {"dropout": rng} if train else None
        logits, mutated = state.apply_fn(
            {"params": params}, ids, train=train,
            mutable=["losses", "counters"], rngs=rngs)
        # `loss`: the vocab-wide work after the model (PERF.md section 3)
        with jax.named_scope("loss"):
            # shift: predict ids[:, 1:] from logits[:, :-1]
            tgt = ids[:, 1:]
            if isinstance(logits, TpShardedLogits):
                # vocab-parallel head (explicit TP): Megatron parallel-vocab
                # CE over the local logit columns — two (B, S, 2)-sized
                # model-axis stats instead of a vocab-scale logits gather
                # (parallel/collectives.tp_parallel_cross_entropy). Same
                # train and eval path.
                per_tok, predicted = tp_parallel_cross_entropy(
                    logits.map_local(lambda x: x[:, :-1]), tgt)
            else:
                lg = logits[:, :-1].astype(jnp.float32)
                per_tok = optax.softmax_cross_entropy_with_integer_labels(
                    lg, tgt)
                predicted = jnp.argmax(lg, axis=-1) == tgt
            w = batch["weight"][:, None] * jnp.ones_like(per_tok)
            wsum = w.sum()
            loss = (per_tok * w).sum() / jnp.maximum(wsum, 1.0)
            if self.aux_loss_weight:
                aux_leaves = jax.tree_util.tree_leaves(
                    mutated.get("losses", {}))
                if aux_leaves:
                    aux = (sum(jnp.asarray(a).mean() for a in aux_leaves)
                           / len(aux_leaves))
                    loss = loss + self.aux_loss_weight * aux
            correct = (predicted * w).sum()
            metrics = {"loss_sum": (per_tok * w).sum(), "correct": correct,
                       "weight": wsum}
        counters = step_counters(mutated.get("counters", {}))
        if counters:
            metrics["counters"] = counters
        return loss, (metrics, state.batch_stats)


@dataclasses.dataclass
class MoeLanguageModelingTask(LanguageModelingTask):
    """Causal LM over an MoE model (models/moe.py): the base CE loss plus the
    Switch-style router load-balancing loss the model sows (weight 0.01)."""

    aux_loss_weight: float = 0.01


@dataclasses.dataclass
class MaskedLMTask(Task):
    """BERT masked-LM (BASELINE.json:11, seq-len 512).

    Standard BERT recipe, applied ON DEVICE inside the compiled step: select
    15% of positions; of those 80% -> [MASK], 10% -> random token, 10% ->
    unchanged; loss only on selected positions. "correct" is masked-token
    top-1. Batch: {"input_ids": (B, S), "weight": (B,)}.
    """

    mask_token_id: int = 103  # BERT-base [MASK]
    vocab_size: int = 30522
    mask_prob: float = 0.15
    compute_dtype: Any = jnp.float32

    def loss_and_metrics(self, state, params, batch, rng, train):
        ids = batch["input_ids"]
        k_sel, k_act, k_rand = jax.random.split(rng, 3)
        selected = jax.random.bernoulli(k_sel, self.mask_prob, ids.shape)
        action = jax.random.uniform(k_act, ids.shape)
        masked = jnp.where(action < 0.8, self.mask_token_id,
                           jnp.where(action < 0.9,
                                     jax.random.randint(k_rand, ids.shape, 0,
                                                        self.vocab_size),
                                     ids))
        inputs = jnp.where(selected, masked, ids)

        rngs = {"dropout": jax.random.fold_in(rng, 1)} if train else None
        logits = state.apply_fn({"params": params}, inputs, train=train,
                                rngs=rngs)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), ids)
        w = selected.astype(jnp.float32) * batch["weight"][:, None]
        wsum = w.sum()
        loss = (per_tok * w).sum() / jnp.maximum(wsum, 1.0)
        correct = ((jnp.argmax(logits, axis=-1) == ids) * w).sum()
        metrics = {"loss_sum": (per_tok * w).sum(), "correct": correct,
                   "weight": wsum}
        return loss, (metrics, state.batch_stats)


def step_counters(sown) -> Metrics:
    """What a model sowed into its ``"counters"`` collection (one scalar per
    module and name, e.g. `models.moe.HeldExpertsMoe`), folded to one value
    per name for the step: the sum over modules, or, for a name ending in
    ``_max_over_mean``, the worst module. They ride the step's metrics in a
    dict of their own, ``metrics["counters"]``, so the loop fetches them with
    the loss at a print boundary and not apart. A model that sows nothing
    adds nothing."""
    by_name: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(sown):
        name = next(k.key for k in reversed(path) if hasattr(k, "key"))
        by_name.setdefault(name, []).append(jnp.asarray(leaf, jnp.float32))
    return {name: (jnp.max if name.endswith("_max_over_mean") else jnp.sum)(
        jnp.stack(leaves)) for name, leaves in by_name.items()}


def zero_metrics() -> Metrics:
    return {"loss_sum": jnp.zeros(()), "correct": jnp.zeros(()),
            "weight": jnp.zeros(())}


def add_metrics(a: Metrics, b: Metrics) -> Metrics:
    """``a + b``, name by name. Both sides hold the same names: a sum that
    starts from `zero_metrics` cannot take a step's ``"counters"``, and says
    so rather than let them go."""
    if jax.tree_util.tree_structure(a) != jax.tree_util.tree_structure(b):
        raise ValueError(
            f"add_metrics: {sorted(a)} and {sorted(b)} differ; a model's "
            "step counters are summed from the first step's metrics "
            "(Trainer.train_epoch, Trainer.evaluate), not from zero_metrics "
            "(the microbatch loops of grad_accum > 1 do not carry them)")
    return jax.tree_util.tree_map(jnp.add, a, b)


def summarize(metrics: Metrics) -> Tuple[float, float]:
    """(mean loss, accuracy %) from weighted sums — the reference's
    global_loss/global_acc math (ref :258-259)."""
    total = float(metrics["weight"])
    if total == 0:
        return float("nan"), float("nan")
    return (float(metrics["loss_sum"]) / total,
            100.0 * float(metrics["correct"]) / total)
