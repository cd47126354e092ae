"""Mixture-of-Experts MLP with expert parallelism over the mesh ``expert`` axis.

No analogue in the reference (ResNet-only; SURVEY.md §2c "EP: absent — note as
extension"); this is the extension, built the TPU way: token-choice top-k
routing with fixed capacity per expert, so every shape is static and the
expert matmuls are einsums XLA tiles onto the MXU. With the stacked expert
weights sharded ``P("expert", ...)``, XLA lowers the dispatch/return to
all-to-alls over the ``expert`` mesh axis — expert parallelism falls out of
layout, exactly like gradient sync falls out of batch sharding.

Two dispatch formulations behind one interface (``dispatch_mode``):

* ``"sorted"`` (default) — argsort assignments by expert id (stable,
  first-choice-major, so priority matches the k-round semantics), compute
  each assignment's rank within its expert segment, drop ranks >= capacity,
  then scatter-add tokens into the (E*C, d) expert buffer and gather-combine
  back. Memory is O(S*k) index vectors + the (E, C, d) buffers — no
  (B, S, E, C) tensor, so 32+ experts and S=4096 fit on one chip.
* ``"einsum"`` — the original dense one-hot dispatch/combine tensors
  ((B, S, E, C): linear in tokens but carrying the S x E x C blowup). Kept
  as the parity oracle; preferable only for tiny expert counts.

Load balancing: the standard Switch-Transformer auxiliary loss
(num_experts * Σ_e fraction_tokens_e * fraction_router_prob_e), sown into the
``"losses"`` collection; `MoeLanguageModelingTask` adds it to the CE loss.
Tokens overflowing an expert's capacity are dropped (their combine weight is
zero) — the residual path carries them unchanged, the standard behavior.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops.grouped_product import (
    grouped_product,
    grouped_product_backend_supported,
    grouped_product_supports,
)
from ..parallel.mesh import EXPERT
from ..parallel.sharding import PartitionRules
from .layers import VocabPaddingMixin
from .registry import register_model
from jax.sharding import PartitionSpec as P

Dtype = Any


class MoeMlp(nn.Module):
    """Top-k token-choice MoE feed-forward (drop-in for MlpBlock)."""

    num_experts: int
    hidden_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    activation: Callable = nn.gelu
    router_noise: float = 0.0  # jitter std during training, 0 = off
    dispatch_mode: str = "sorted"  # "sorted" (scalable) | "einsum" (oracle)

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        # GShard-style GROUP-WISE dispatch: each batch row is a routing group
        # with its own capacity ceil(S*k/E * cf). Capacity scales with top_k:
        # k assignments are made per token, so total slots must cover S*k
        # routing decisions, not S.
        b, s, d = x.shape
        e = self.num_experts
        cap = max(1, int(np.ceil(s * self.top_k / e * self.capacity_factor)))

        router = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          param_dtype=self.param_dtype, name="router")
        logits = router(x.astype(jnp.float32))  # (B, S, E), fp32 softmax
        if self.router_noise and not deterministic:
            key = self.make_rng("dropout")
            logits = logits + self.router_noise * jax.random.normal(
                key, logits.shape)
        probs = jax.nn.softmax(logits, axis=-1)

        wi = self.param("wi", nn.initializers.lecun_normal(batch_axis=(0,)),
                        (e, d, self.hidden_dim), self.param_dtype)
        wo = self.param("wo", nn.initializers.lecun_normal(batch_axis=(0,)),
                        (e, self.hidden_dim, d), self.param_dtype)

        if self.dispatch_mode == "sorted":
            xin, combine_fn, frac_tokens = self._dispatch_sorted(
                x, probs, b, s, d, e, cap)
        else:
            xin, combine_fn, frac_tokens = self._dispatch_einsum(
                x, probs, b, s, d, e, cap)

        # --- auxiliary load-balancing loss (Switch eq. 4, over all tokens) -
        frac_probs = probs.reshape(-1, e).mean(0)
        aux = e * jnp.sum(frac_tokens * frac_probs) / self.top_k
        self.sow("losses", "moe_aux", aux)

        # --- expert computation (stacked weights, EP via sharding) ---------
        h = self.activation(jnp.einsum("becd,edh->bech", xin,
                                       wi.astype(self.dtype)))
        out = jnp.einsum("bech,ehd->becd", h, wo.astype(self.dtype))
        return combine_fn(out)

    def _topk(self, probs, b, s, e):
        """(expert_ids, gates) per assignment, flattened FIRST-CHOICE-MAJOR
        (all k=0 assignments before any k=1), matching the round-robin
        priority of the einsum oracle's k-round loop."""
        gates, choice = jax.lax.top_k(probs, self.top_k)  # (B, S, K)
        eids = choice.transpose(0, 2, 1).reshape(b, self.top_k * s)
        gvals = gates.transpose(0, 2, 1).reshape(b, self.top_k * s)
        return eids.astype(jnp.int32), gvals

    def _dispatch_sorted(self, x, probs, b, s, d, e, cap):
        """Sort-based dispatch: rank each assignment within its expert via a
        stable argsort, drop ranks >= capacity, scatter tokens into the
        (E*C, d) buffer. No (B, S, E, C) tensor anywhere (VERDICT r3 #8)."""
        n = self.top_k * s
        eids, gates = self._topk(probs, b, s, e)  # (B, N)

        # rank of each assignment within its expert segment
        sort_idx = jnp.argsort(eids, axis=-1, stable=True)  # (B, N)
        sorted_e = jnp.take_along_axis(eids, sort_idx, axis=-1)
        counts = jnp.sum(jax.nn.one_hot(eids, e, dtype=jnp.int32), axis=1)
        starts = jnp.concatenate(
            [jnp.zeros((b, 1), jnp.int32),
             jnp.cumsum(counts, axis=-1)[:, :-1]], axis=-1)  # (B, E)
        ranks_sorted = (jnp.arange(n, dtype=jnp.int32)[None, :]
                        - jnp.take_along_axis(starts, sorted_e, axis=-1))
        inv = jnp.argsort(sort_idx, axis=-1, stable=True)
        ranks = jnp.take_along_axis(ranks_sorted, inv, axis=-1)  # (B, N)

        kept = ranks < cap
        # overflow assignments land in a sacrificial bin at E*cap
        dest = jnp.where(kept, eids * cap + ranks, e * cap)  # (B, N)

        tok = jnp.arange(n, dtype=jnp.int32) % s  # k-major: token of slot n
        x_gath = x.astype(self.dtype)[:, tok]  # (B, N, d)
        brow = jnp.arange(b, dtype=jnp.int32)[:, None]
        xin_flat = jnp.zeros((b, e * cap + 1, d), self.dtype
                             ).at[brow, dest].add(x_gath)
        xin = xin_flat[:, :e * cap].reshape(b, e, cap, d)

        kept_onehot = (jax.nn.one_hot(eids, e, dtype=jnp.float32)
                       * kept[..., None].astype(jnp.float32))
        frac_tokens = kept_onehot.sum(1).mean(0) / s  # == mean over (B*S)

        def combine_fn(out):  # out: (B, E, C, d)
            out_flat = jnp.concatenate(
                [out.reshape(b, e * cap, d),
                 jnp.zeros((b, 1, d), out.dtype)], axis=1)
            y_n = out_flat[brow, dest]  # (B, N, d); overflow bin reads zeros
            y_n = y_n * gates[..., None].astype(self.dtype)
            return y_n.reshape(b, self.top_k, s, d).sum(1)

        return xin, combine_fn, frac_tokens

    def _dispatch_einsum(self, x, probs, b, s, d, e, cap):
        """The original dense one-hot formulation — (B, S, E, C) dispatch/
        combine tensors. Parity oracle for the sorted path; carries the
        S x E x C memory bill, so use it only at small E."""
        combine = jnp.zeros((b, s, e, cap), jnp.float32)
        fill = jnp.zeros((b, e), jnp.int32)  # slots taken, per group
        remaining = probs
        total_dispatch = jnp.zeros((b, s, e), jnp.float32)
        for _ in range(self.top_k):
            choice = jnp.argmax(remaining, axis=-1)  # (B, S)
            onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # (B, S, E)
            gate = (probs * onehot).sum(-1)  # (B, S)
            # position of each token within its expert's buffer (per group):
            pos = (jnp.cumsum(onehot, axis=1) - 1.0) + fill[:, None, :]
            pos_tok = (pos * onehot).sum(-1).astype(jnp.int32)  # (B, S)
            keep = pos_tok < cap
            slot = jax.nn.one_hot(pos_tok, cap, dtype=jnp.float32)  # (B, S, C)
            disp = onehot * keep[..., None]  # (B, S, E)
            combine = combine + (gate[..., None, None] * disp[..., None]
                                 * slot[..., None, :])
            total_dispatch = total_dispatch + disp
            fill = fill + disp.sum(1).astype(jnp.int32)
            remaining = remaining * (1.0 - onehot)  # mask chosen expert

        frac_tokens = total_dispatch.reshape(-1, e).mean(0)
        dispatch = (combine > 0).astype(self.dtype)  # (B, S, E, C)
        xin = jnp.einsum("bsec,bsd->becd", dispatch,
                         x.astype(self.dtype))  # (B, E, C, d)

        def combine_fn(out):
            return jnp.einsum("bsec,becd->bsd", combine.astype(self.dtype),
                              out)

        return xin, combine_fn, frac_tokens


# -- a table's rows <-> sorted assignment rows --------------------------------
#
# One 0/1 operator G and its transpose, both written as GATHERS. Sorted
# assignment ``start + i`` reads row ``index[i]`` of a table; seen from the
# table, the sorted positions that read row ``r`` are ``rank_of[:, r]``
# (int32[k, m]: every row is read k times) or the one position ``rank_of[r]``
# (int32[m]: the inverse of a permutation). G makes the window's rows, zero
# from position ``stop`` on; its transpose sums, for each row of the table,
# the window's rows before ``stop`` that read it. Each is the other's
# backward, so differentiating a gather never makes a scatter-add: on a TPU
# the scatter-add of 20,480 rows of 2,048 into 8,192 took 1.7 ms where its
# bytes would take 0.2 (PERF.md section 5).
#
# Neither closes over anything, and `jax.jit` keeps each body by its shapes:
# the dozens of call sites of a program (layers x forward, remat and
# backward) share one jaxpr and one lowered function, which every process
# pays for once (PERF.md section 6, PR 34). The integer operands take no
# cotangent.

@jax.jit
def _take_rows(table, index, start, stop):
    rows = index.shape[0]
    inside = start + jnp.arange(rows) < stop
    return jnp.where(inside.reshape((rows,) + (1,) * (table.ndim - 1)),
                     jnp.take(table, index, axis=0, mode="clip"), 0)


# What `_sum_rows` gathers at once: k x block rows, written by the gather and
# read back by the sum. Under this size XLA keeps them in the chip's fast
# memory beside the sorted rows; all of the hybrid cell's 81,920 x 2,048
# (335 MB) would be written to HBM and read again (PERF.md section 6, PR 47).
_GATHERED_AT_ONCE = 16 * 2 ** 20


def _blocks(m: int, gathered_row_bytes: int) -> int:
    """Into how many equal blocks the table's ``m`` rows go so that a block's
    gathered rows fit `_GATHERED_AT_ONCE`: the least divisor of ``m`` that
    does (``m`` itself at the worst)."""
    return next((n for n in range(1, m) if m % n == 0
                 and m // n * gathered_row_bytes <= _GATHERED_AT_ONCE), m)


@jax.jit
def _sum_rows(sorted_rows, rank_of, start, stop):
    def gathered(rank_of):
        inside = (rank_of >= start) & (rank_of < stop)
        # a choice outside the window reads row 0 and is dropped by the
        # `where` AFTER the read, never by a product with zero: a row past
        # the last group may hold anything, and none is ever indexed
        picked = jnp.take(sorted_rows, jnp.where(inside, rank_of - start, 0),
                          axis=0, mode="clip")
        return jnp.where(inside.reshape(
            inside.shape + (1,) * (sorted_rows.ndim - 1)), picked, 0)

    if rank_of.ndim == 1:
        return gathered(rank_of)
    # the k rows that read one row of the table: summed in float32 and
    # rounded once, a block of the table's rows at a time. Choice-major,
    # (k, m): an (m, k, d) table would be re-tiled to 16 choices and copied
    k, m = rank_of.shape
    n = _blocks(m, k * sorted_rows.dtype.itemsize
                * int(np.prod(sorted_rows.shape[1:])))
    summed = jax.lax.map(
        lambda ranks: gathered(ranks).astype(jnp.float32).sum(0).astype(
            sorted_rows.dtype),
        rank_of.reshape(k, n, m // n).swapaxes(0, 1))
    return summed.reshape((m,) + sorted_rows.shape[1:])


@jax.custom_vjp
def take_rows(table, index, rank_of, start, stop):
    """G: ``table[index]``, the rows of sorted assignments ``start ..
    start + len(index) - 1``, zero from position ``stop`` on."""
    return _take_rows(table, index, start, stop)


@jax.custom_vjp
def sum_rows(sorted_rows, index, rank_of, start, stop):
    """G's transpose: for each row of the table, the sum of the rows of
    sorted assignments ``start .. stop - 1`` that read it."""
    return _sum_rows(sorted_rows, rank_of, start, stop)


def _take_rows_fwd(table, *operands):
    return take_rows(table, *operands), operands


def _take_rows_bwd(operands, cotangent):
    return (sum_rows(cotangent, *operands), None, None, None, None)


def _sum_rows_fwd(sorted_rows, *operands):
    return sum_rows(sorted_rows, *operands), operands


def _sum_rows_bwd(operands, cotangent):
    return (take_rows(cotangent, *operands), None, None, None, None)


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


# what `HeldExpertsMoe`'s router calls the results of its sorts, for a remat
# policy that keeps them (`save_only_these_names`): both outputs of the one
# `top_k` (its sort goes only if neither is wanted again), the sorted order,
# the groups' ends, the order's inverse: integers but for the probabilities,
# 1.3 MB a layer at 8,192 tokens x 10, which a second pass could only make
# again as they were. An identity under no policy or another
ROUTE_NAMES = ("moe_top_p", "moe_top_e", "moe_order", "moe_ends",
               "moe_rank_of")


def _named_top_k(probs, k: int):
    return tuple(map(checkpoint_name, jax.lax.top_k(probs, k),
                     ("moe_top_p", "moe_top_e")))


# `lax.top_k` with both results named. Its derivative is `lax.top_k`'s own
# (the tangent's entries at the chosen indices), read through the NAMED
# indices: lax's rule reads them off the `top_k` it differentiates, which a
# policy that keeps names would have to run again for them
named_top_k = jax.custom_jvp(_named_top_k, nondiff_argnums=(1,))


@named_top_k.defjvp
def _named_top_k_jvp(k, primals, tangents):
    top_p, top_e = _named_top_k(*primals, k)
    return (top_p, top_e), (
        jnp.take_along_axis(tangents[0], top_e, axis=-1),
        np.zeros(top_e.shape, jax.dtypes.float0))


class HeldExpertsMoe(nn.Module):
    """One chip's share of a dropless top-k expert layer (the model-configs
    guide's section 4): the router is ``num_experts`` wide and keeps its
    ``top_k`` (of all experts, or of the experts of its ``topk_group`` best
    groups), this module holds experts ``first_expert ..
    first_expert + num_experts_held - 1`` and returns the part of the layer's
    result that THOSE experts give. What the absent experts would add is left
    out: there is no exchange here and nothing stands in for one.

    Dropless on static shapes. The ``tokens * top_k`` assignments are sorted
    by expert with the held experts first, so the held ones are a prefix of
    the sorted order, grouped by expert, and the absent ones fall past the
    last group; gated-SiLU experts run as grouped products
    (`lax.ragged_dot`) over the first quarter of the sorted order (this
    share expects 1/16 of all assignments under balanced routing), and over
    the other quarters, one at a time, only where held assignments reach
    them (a `lax.cond` on the count): no assignment to a held expert is ever
    dropped, however many land on one, and the worst routing costs time,
    not memory. With ``num_experts_held == num_experts`` (a whole layer on
    one chip, models/sdar.py) every assignment is held, and the products run
    over the whole sorted order in one pass, with no `lax.cond`; that pass
    takes `ops.grouped_product`'s kernel where `expert_path` says so.
    ``moe_dropped_assignments`` (held assignments minus those a
    group covered) is zero by construction; it is counted anyway and checked
    by the tests and the benchmark.

    No row is scattered, forward or backward. A window's rows come IN by the
    row (`take_rows`: ``x[token]``, zeros past the last group) and go BACK
    by the token (`sum_rows`): the inverse of the sorting permutation, made
    once a layer, says where each of a token's ``top_k`` choices stands in
    the sorted order, and the token gathers those that lie in the window
    and sums them in float32, rounded once. Each is the other's backward;
    the router's weights take the same pair (read where the window needs
    them, their gradient gathered back by assignment).

    Counters, sown into ``"counters"`` (training/tasks.py folds them into the
    step's metrics): ``moe_held_assignments``, ``moe_dropped_assignments``,
    ``moe_expert_load_max_over_mean`` (largest group over the mean group).
    """

    num_experts: int
    num_experts_held: int
    top_k: int
    expert_dim: int
    first_expert: int = 0
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    # the routing rule beside top-k of the softmax, renormalised: with
    # ``n_group`` > 1 the experts lie in that many groups of consecutive
    # ids, a group's score is its largest probability, only the
    # ``topk_group`` best groups' experts can be chosen (group-limited
    # greedy selection); ``norm_topk_prob`` false keeps the chosen
    # probabilities as they are; all are multiplied by the scaling factor
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the router's initial scale (the experts' is 0.02 always)
    router_init_std: float = 0.02

    def expert_path(self, tokens: int, hidden: int) -> str:
        """How the grouped products over ``tokens`` rows of ``hidden`` run:
        ``"kernel"``, `ops.grouped_product`, which reads an expert's weights
        once while its rows pass, where everything it needs is visible at
        trace time (the rule `SlotEngine.kv_path` follows): every expert
        held, so that ONE pass covers the sorted order and nothing
        differentiates it or walks it under a `lax.cond` (a share's does
        both, and the kernel's transposes are checked nowhere); a TPU with
        one device; and shapes of whole tiles. Else ``"xla"``, `lax.ragged_dot`. No option
        selects it; the ``compile`` span of ``paged_decode`` carries it."""
        rows = tokens * self.top_k
        kernel = (self.num_experts_held == self.num_experts
                  and grouped_product_backend_supported()
                  and grouped_product_supports(rows, hidden, self.expert_dim,
                                               self.dtype)
                  and grouped_product_supports(rows, self.expert_dim, hidden,
                                               self.dtype))
        return "kernel" if kernel else "xla"

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        t, k, held = b * s, self.top_k, self.num_experts_held
        if self.num_experts % self.n_group \
                or not 0 < self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.num_experts} experts in {self.n_group} groups of "
                f"which {self.topk_group} stay")
        if not 0 < held <= self.num_experts - self.first_expert:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + held - 1}"
                f" are not among {self.num_experts}")
        init = nn.initializers.normal(stddev=0.02)
        router = self.param(
            "router", nn.initializers.normal(stddev=self.router_init_std),
            (d, self.num_experts), self.param_dtype)
        w_gate = self.param("gate", init, (held, d, self.expert_dim),
                            self.param_dtype)
        w_up = self.param("up", init, (held, d, self.expert_dim),
                          self.param_dtype)
        w_down = self.param("down", init, (held, self.expert_dim, d),
                            self.param_dtype)
        xf = x.reshape(t, d)
        all_rows = t * k
        # a share walks the sorted order a quarter at a time; where every
        # expert is held (a static fact) every assignment is, and the
        # grouped products run over the whole order at once
        rows = all_rows if held == self.num_experts \
            else min(all_rows, max(8, -(-all_rows // 4)))

        with jax.named_scope("moe_route"):
            # the router in float32 over ALL experts: a top-k is a
            # comparison, and a bf16 product would reorder near-ties
            probs = jax.nn.softmax(jnp.dot(
                xf.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), axis=-1)
            if self.n_group > 1:
                # `top_k` puts the lower index first among equals, here and
                # below: a tie goes to the lower group, the lower expert
                in_groups = probs.reshape(t, self.n_group, -1)
                _, best = jax.lax.top_k(in_groups.max(-1), self.topk_group)
                stays = (best[:, :, None] == jnp.arange(self.n_group)).any(1)
                probs = jnp.where(stays[:, :, None], in_groups,
                                  0.0).reshape(t, self.num_experts)
            top_p, top_e = named_top_k(probs, k)
            kept = top_p / top_p.sum(-1, keepdims=True) \
                if self.norm_topk_prob else top_p
            if self.routed_scaling_factor != 1.0:
                kept = kept * self.routed_scaling_factor
            # held experts -> 0..held-1, absent ones -> held..E-1
            local = ((top_e - self.first_expert) % self.num_experts
                     ).reshape(t * k)
            order = jnp.argsort(local, stable=True).astype(jnp.int32)
            ends = checkpoint_name(jnp.searchsorted(
                local[order], jnp.arange(held + 1),
                side="left").astype(jnp.int32), "moe_ends")
            group_sizes = ends[1:] - ends[:-1]
            n_held = ends[-1]
            # where assignment (token, choice) stands in the sorted order:
            # the order's inverse, made once a layer, through which the
            # token side gathers what a scatter by `order` would add
            rank_of = checkpoint_name(
                jnp.argsort(order, stable=False).astype(jnp.int32),
                "moe_rank_of")
            rank_by_choice = rank_of.reshape(t, k).T
            # whole windows: what pads the last one lies past `n_held`
            order = checkpoint_name(
                jnp.pad(order, (0, -all_rows % rows)), "moe_order")
            kept = kept.reshape(all_rows)
        product = grouped_product if self.expert_path(t, d) == "kernel" \
            else jax.lax.ragged_dot

        def experts_on(start):
            """The part of the result that sorted assignments ``start ..
            start + rows - 1`` give, and how many of them a group covered."""
            in_group = start + jnp.arange(rows) < n_held
            stop = jnp.minimum(start + rows, n_held)
            which = jax.lax.dynamic_slice(order, (start,), (rows,))
            token = which // k
            with jax.named_scope("moe_dispatch"):
                # rows past the last group are masked on the way in as on
                # the way out (`take_rows` gives zeros from `stop` on, and
                # its transpose reads no row from there on): a grouped
                # product leaves them unwritten, in its transpose too, and
                # what lies there must not reach a token's gradient
                xin = take_rows(xf, token, rank_by_choice, start,
                                stop).astype(self.dtype)
            with jax.named_scope("moe_experts"):
                sizes = jnp.clip(ends[1:], start, start + rows) \
                    - jnp.clip(ends[:-1], start, start + rows)
                grouped = lambda a, w: product(  # noqa: E731
                    a, w.astype(self.dtype), sizes)
                mid = nn.silu(grouped(xin, w_gate)) * grouped(xin, w_up)
                out = grouped(mid, w_down)
            with jax.named_scope("moe_dispatch"):
                # a row past the last group holds no expert's result: it is
                # masked BEFORE the product with its weight, so that the
                # weight's gradient (row . cotangent) never multiplies what
                # lies there, and the weight itself is masked too
                weight = take_rows(kept, which, rank_of, start, stop)
                out = jnp.where(in_group[:, None], out, 0) \
                    * weight[:, None].astype(out.dtype)
                # each token sums the rows of its own choices (float32,
                # rounded once), where a scatter would add row by row
                y = sum_rows(out, token, rank_by_choice, start, stop)
            return y, sizes.sum()

        # the first quarter of the sorted order always; the other quarters
        # only where held assignments reach them, one at a time and
        # rematerialised, so the worst routing costs time and not memory
        y, covered = experts_on(jnp.int32(0))
        if all_rows > rows:
            def rest():
                def more(carry, start):
                    y_more, n_more = jax.checkpoint(experts_on)(start)
                    return (carry[0] + y_more, carry[1] + n_more), None
                return jax.lax.scan(
                    more, (jnp.zeros_like(y), jnp.zeros_like(covered)),
                    jnp.arange(rows, all_rows, rows))[0]
            y_rest, n_rest = jax.lax.cond(
                n_held > rows, rest,
                lambda: (jnp.zeros_like(y), jnp.zeros_like(covered)))
            y, covered = y + y_rest, covered + n_rest

        mean = jnp.maximum(n_held, 1).astype(jnp.float32) / held
        self.sow("counters", "moe_held_assignments",
                 n_held.astype(jnp.float32))
        self.sow("counters", "moe_dropped_assignments",
                 (n_held - covered).astype(jnp.float32))
        self.sow("counters", "moe_expert_load_max_over_mean",
                 group_sizes.max().astype(jnp.float32) / mean)
        return y.reshape(b, s, d).astype(self.dtype)


def moe_rules() -> PartitionRules:
    """Expert-parallel rules: stacked expert weights split over ``expert``;
    the router stays replicated (it is tiny and every token needs it)."""
    return PartitionRules([
        (r"moe/wi", P(EXPERT, None, None)),
        (r"moe/wo", P(EXPERT, None, None)),
    ])


class MoeTransformerBlock(nn.Module):
    """Pre-LN block with the MoE feed-forward in place of the dense MLP."""

    num_heads: int
    head_dim: int
    num_experts: int
    mlp_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    dropout_rate: float = 0.0
    layernorm_epsilon: float = 1e-5
    attention_fn: Optional[Callable] = None
    router_noise: float = 0.0
    dispatch_mode: str = "sorted"

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        from .layers import MultiHeadAttention, dot_product_attention

        ln_kw = dict(epsilon=self.layernorm_epsilon, dtype=self.dtype,
                     param_dtype=self.param_dtype)
        y = nn.LayerNorm(**ln_kw, name="ln1")(x)
        y = MultiHeadAttention(
            num_heads=self.num_heads, head_dim=self.head_dim,
            dtype=self.dtype, param_dtype=self.param_dtype,
            dropout_rate=self.dropout_rate,
            attention_fn=self.attention_fn or dot_product_attention,
            name="attn")(y, mask=mask, deterministic=deterministic)
        x = x + y
        y = nn.LayerNorm(**ln_kw, name="ln2")(x)
        y = MoeMlp(num_experts=self.num_experts, hidden_dim=self.mlp_dim,
                   top_k=self.top_k, capacity_factor=self.capacity_factor,
                   dtype=self.dtype, param_dtype=self.param_dtype,
                   router_noise=self.router_noise,
                   dispatch_mode=self.dispatch_mode,
                   name="moe")(y, deterministic=deterministic)
        return x + y


class GPT2MoELMHead(VocabPaddingMixin, nn.Module):
    """GPT-2-style causal LM with MoE feed-forwards on alternating layers
    (the Switch/GShard layout: dense and MoE blocks interleave)."""

    vocab_size: int = 50257
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 2  # layer i is MoE iff i % moe_every == moe_every - 1
    max_position: int = 1024
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    layernorm_epsilon: float = 1e-5
    attention_fn: Optional[Callable] = None
    router_noise: float = 0.0
    dispatch_mode: str = "sorted"
    # jax.checkpoint the DENSE blocks only: MoE blocks sow the router
    # aux-loss into the "losses" collection, which remat would complicate;
    # half the layers is still half the activation memory.
    remat: bool = False
    # Megatron-style vocab padding for TP (see models/gpt2.py). 0 = exact.
    pad_vocab_to_multiple_of: int = 0

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, train: bool = False):
        from .layers import TransformerBlock, causal_mask, dot_product_attention

        b, s = input_ids.shape
        wte = nn.Embed(self.padded_vocab, self.hidden_dim, dtype=self.dtype,
                       param_dtype=self.param_dtype,
                       embedding_init=nn.initializers.normal(stddev=0.02),
                       name="wte")
        x = wte(input_ids)
        x = x + nn.Embed(self.max_position, self.hidden_dim, dtype=self.dtype,
                         param_dtype=self.param_dtype,
                         embedding_init=nn.initializers.normal(stddev=0.01),
                         name="wpe")(jnp.arange(s)[None, :])

        attn_fn = self.attention_fn or dot_product_attention
        uses_kernel = attn_fn is not dot_product_attention
        # kernel paths own causal structure — they get only the padding
        # mask (flash applies it blockwise); einsum gets causal & padding
        if uses_kernel:
            mask = (attention_mask[:, None, None, :].astype(bool)
                    if attention_mask is not None else None)
        else:
            mask = causal_mask(s)
            if attention_mask is not None:
                mask = mask & attention_mask[:, None, None, :].astype(bool)

        head_dim = self.hidden_dim // self.num_heads
        for i in range(self.depth):
            if i % self.moe_every == self.moe_every - 1:
                x = MoeTransformerBlock(
                    num_heads=self.num_heads, head_dim=head_dim,
                    num_experts=self.num_experts,
                    mlp_dim=4 * self.hidden_dim, top_k=self.top_k,
                    capacity_factor=self.capacity_factor, dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    layernorm_epsilon=self.layernorm_epsilon,
                    attention_fn=self.attention_fn,
                    router_noise=self.router_noise,
                    dispatch_mode=self.dispatch_mode,
                    name=f"block{i}")(x, mask=mask, deterministic=not train)
            else:
                dense_cls = (nn.remat(TransformerBlock) if self.remat
                             else TransformerBlock)
                x = dense_cls(
                    num_heads=self.num_heads, head_dim=head_dim,
                    mlp_dim=4 * self.hidden_dim, dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    layernorm_epsilon=self.layernorm_epsilon,
                    attention_fn=attn_fn,
                    name=f"block{i}")(x, mask=mask, deterministic=not train)

        x = nn.LayerNorm(epsilon=self.layernorm_epsilon, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="ln_f")(x)
        from .layers import mask_vocab_padding

        return mask_vocab_padding(wte.attend(x).astype(jnp.float32),
                                  self.vocab_size)

    @staticmethod
    def partition_rules() -> PartitionRules:
        from .layers import tp_fsdp_rules

        return moe_rules() + tp_fsdp_rules()


@register_model("gpt2_moe")
def gpt2_moe(**kw) -> GPT2MoELMHead:
    """GPT-2-small-sized MoE LM (8 experts, top-2, MoE every other layer)."""
    return GPT2MoELMHead(**kw)
