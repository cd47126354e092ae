"""The gated delta rule in chunks: the recurrence of a Gated DeltaNet layer
(Yang et al., "Gated Delta Networks", arXiv:2412.06464) computed 64 positions
at a time. Two forms of one result: in a one-device program on a TPU the
kernel pair of `gdn_rule_kernels` (`gdn_rule_fwd`, `gdn_rule_bwd`: a chunk's
intermediates in VMEM, the rule's own backward); everywhere else XLA
operations, forward and (by XLA's own transpose) backward, which is also the
tests' second oracle beside the recurrence. `gated_delta_rule` chooses by
what it can observe of the backend, the shapes and the trace: no option.
`gated_delta_mixer` is the entry a Gated DeltaNet layer calls: the mixer
between its two projections (the causal convolution with its SiLU, the
rule, the gated output norm), handed the in-projection's output whole and
the parameters the layer holds anyway. On the kernel path the convolution
is one pass that finds q | k | v by column (`gdn_conv_kernels`), q's and
k's l2 norm, the key heads' repeat and the gated output norm happen in the
rule's kernels' VMEM, z is read where it stands, and neither a slice of the
projection nor a float32 table ever exists; its other form is those lines
as XLA operations around `gated_delta_rule`.

Per head, with a state ``S`` of shape (key, value) starting at zero::

    S   = exp(g_t) * S                  # decay, g_t <= 0
    u_t = (v_t - S^T k_t) * beta_t      # what the state does not hold yet
    S   = S + k_t u_t^T
    o_t = S^T q_t

`gated_delta_rule_stepwise` is that loop, position by position (the tests'
oracle for both chunked forms, one `lax.scan` step a position). The chunked
form is the WY representation the reference implementations use: within a
chunk the ``u_t`` solve a unit lower-triangular system ``(I + A) U = beta *
(V - exp(g) K S_in)`` with ``A[i, j] = beta_i (k_i . k_j) exp(g_i - g_j)`` for
``j < i``, so one triangular solve gives every ``u_t`` of the chunk from the
state the chunk started with, and the state moves once a chunk. In the XLA
form everything that does not need the incoming state (the solve, the
in-chunk scores) is computed for all chunks at once and the state is carried
by a `lax.scan` over chunks, not unrolled; the kernels make the same
quantities a chunk at a time and invert ``I + A`` by block substitution
(their module has why that is exact).

Precision, both forms: the state, the decays and the solve are float32
whatever the inputs' dtype, and every product is taken at PRECISION (HIGHEST:
on a TPU a float32 product otherwise runs as one bf16 pass, which would make
the state bf16 in all but name). PARITY.md has the boundary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .gdn_conv_kernels import gdn_conv_supports
from .gdn_rule_kernels import (
    L2_EPSILON, gated_delta_mixer_kernels, gated_delta_rule_kernels,
    gdn_rule_backend_supported, gdn_rule_one_device_trace, gdn_rule_supports,
)

CHUNK = 64
PRECISION = lax.Precision.HIGHEST


def gated_delta_rule_stepwise(q, k, v, g, beta):
    """The recurrence itself. q, k: (B, S, H, Dk); v: (B, S, H, Dv); g,
    beta: (B, S, H). Returns (B, S, H, Dv) float32."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    hi = PRECISION

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        held = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=hi)
        u = (v_t - held) * b_t[..., None]
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, u, precision=hi)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=hi)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                      xs)
    return jnp.moveaxis(out, 0, 1)


def gated_delta_rule(q, k, v, g, beta, *, head_block: int):
    """The same result as `gated_delta_rule_stepwise`, CHUNK positions at a
    time. A length that is no multiple of CHUNK is padded with
    positions that leave the state alone (k = v = 0, beta = 0, g = 0).

    Two paths, and no option chooses between them. The kernel pair of
    `gdn_rule_kernels` holds a chunk's intermediates in VMEM and needs no
    ``head_block`` (the argument is checked and otherwise unused there). It
    is taken where all three of its module's gates hold: the backend is a
    TPU, the shapes are those the kernels were compiled for (an even head
    count, head sizes in multiples of 128), and the program being traced is
    one device's (one device in the process, or inside a `shard_map`): GSPMD
    cannot partition a Mosaic kernel, so a multi-device GSPMD program keeps
    the XLA form, which it partitions as any other operations. Everywhere
    else, that XLA form, below: heads are
    independent, and the float32 intermediates of all of them at once are
    what a layer's backward holds most of (3.5 GB for 32 heads of 128 at
    S=8192), so the heads go ``min(head_block, H)`` at a time through a
    `lax.map` whose body is rematerialised: the backward holds one block's
    intermediates and pays the block's forward once more. A head count the
    block does not divide is an error on both paths, not another program."""
    h = q.shape[2]
    block = min(head_block, h)
    if block < 1 or h % block:
        raise ValueError(f"gated_delta_rule: head_block {head_block} does "
                         f"not divide the {h} heads")
    if gdn_rule_backend_supported() \
            and gdn_rule_supports(h, q.shape[-1], v.shape[-1]) \
            and gdn_rule_one_device_trace():
        return gated_delta_rule_kernels(q, k, v, g, beta)

    def blocks(x):   # (B, S, H, ...) -> (H / block, B, S, block, ...)
        x = x.reshape(*x.shape[:2], h // block, block, *x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    # `_chunked_rule` is looked up at call time: the benchmark's test of its
    # layer check replaces it
    out = lax.map(jax.checkpoint(lambda xs: _chunked_rule(*xs)),
                  tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 2).reshape(*q.shape[:3], v.shape[-1])


def causal_conv_silu(x, taps):
    """The depthwise causal convolution of a Gated DeltaNet mixer with its
    SiLU, as XLA operations. x: (B, S, C); taps: (K, C), tap j weighing the
    input K - 1 - j back, zeros before position 0. Float32 inside and
    rounded once, to x's dtype: `gdn_conv_kernels`' contract, and the oracle
    of its tests."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    pre = sum(padded[:, j:j + s] * taps[j].astype(jnp.float32)
              for j in range(k))
    return (pre * jax.nn.sigmoid(pre)).astype(x.dtype)


def gated_delta_mixer(qkvz, taps, g, beta, norm_w, epsilon, *,
                      key_heads: int, head_block: int):
    """A Gated DeltaNet mixer between its two projections. qkvz: (B, S, 2 *
    key_heads * Dk + 2 * H * Dv), the in-projection's output, its columns q
    | k | v | z with q and k at ``key_heads`` heads; taps: (K, the q | k | v
    columns), the convolution's; g, beta: (B, S, H) float32; norm_w: (Dv,),
    the gated norm's weight, and ``epsilon`` its constant. Returns (B, S, H
    * Dv) in qkvz's dtype, what ``out_proj`` reads::

        q | k | v = silu(causal convolution of those columns)    # rounded
        q, k = l2(q) * Dk ** -0.5, l2(k)     # l2(x) = x rsqrt(sum(x x) + 1e-6)
        o = rule(q, k of value head j = those of key head j // (H / key_heads),
                 v, g, beta)
        out = o rsqrt(mean(o o) + epsilon) * norm_w * silu(z)    # rounded

    each of the two parts in float32 and rounded once, at its end. Two
    forms, chosen as `gated_delta_rule` chooses and by the same three gates,
    the shapes' gate asked about the key heads and the convolution's blocks
    too, so that a program has both kernel pairs or neither. The kernels
    read qkvz as it stands: the convolution's pair finds q | k | v by
    column and writes the one table the rule's pair reads, which does the
    second and the last line in VMEM, chunk by chunk, and finds z by
    column; no slice of qkvz and no float32 table of q, k or o, repeated or
    not, is ever in HBM, and their backward writes qkvz's cotangent once
    (`gdn_rule_kernels._mixer_bwd`). Everywhere else the four lines above
    are XLA operations around `gated_delta_rule`'s XLA form: the tests'
    oracle, and what a multi-device GSPMD program runs. The rule itself
    lies under scope ``gdn_rule`` in both."""
    b, s, h = g.shape
    dv = norm_w.shape[-1]
    conv_dim = taps.shape[-1]
    key_dim = (conv_dim - h * dv) // 2            # q's columns, and k's
    dk = key_dim // key_heads
    if gdn_rule_backend_supported() \
            and gdn_rule_supports(h, dk, dv, key_heads) \
            and gdn_conv_supports((key_dim, key_dim, h * dv),
                                  taps.shape[0]) \
            and gdn_rule_one_device_trace():
        return gated_delta_mixer_kernels(qkvz, taps, g, beta, norm_w,
                                         epsilon, key_heads=key_heads)
    qkv = causal_conv_silu(qkvz[..., :conv_dim], taps)
    q, k = (qkv[..., first:first + key_dim].reshape(
        b, s, key_heads, dk).astype(jnp.float32) for first in (0, key_dim))
    v = qkv[..., 2 * key_dim:].reshape(b, s, h, dv)
    l2 = lambda t: t * lax.rsqrt(  # noqa: E731
        jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPSILON)
    q, k = l2(q) * dk ** -0.5, l2(k)
    # value head j reads key head j // (h / key_heads)
    q, k = (jnp.repeat(t, h // key_heads, axis=2) for t in (q, k))
    with jax.named_scope("gdn_rule"):
        o = gated_delta_rule(q, k, v, g, beta, head_block=head_block)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + epsilon)
    o = o * norm_w.astype(jnp.float32) * jax.nn.silu(
        qkvz[..., conv_dim:].reshape(b, s, h, dv).astype(jnp.float32))
    return o.astype(qkvz.dtype).reshape(b, s, h * dv)


def _chunked_rule(q, k, v, g, beta):
    chunk = CHUNK
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (s + pad) // chunk

    def chunked(x):   # (B, S, H, ...) -> (N, B, H, C, ...)
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (chunked(x) for x in (q, k, v, g, beta))
    dot = lambda spec, x, y: jnp.einsum(spec, x, y,  # noqa: E731
                                        precision=PRECISION)

    # decays inside a chunk: gc_i = sum of g up to and including i, so
    # exp(gc_i - gc_j) carries position j's write to position i >= j. The
    # differences are masked BEFORE the exponential: above the diagonal they
    # are positive and would overflow under strong decay.
    gc = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = jnp.where(lower, gc[..., :, None] - gc[..., None, :], 0.0)
    decay = jnp.where(lower, jnp.exp(diff), 0.0)

    k_beta = k * beta[..., None]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strict, dot("nbhik,nbhjk->nbhij", k_beta, k) * decay, 0.0)
    rhs = jnp.concatenate(
        [v * beta[..., None], k_beta * jnp.exp(gc)[..., None]], axis=-1)
    # (I + A) X = rhs: unit lower triangular, one solve for both halves
    solved = lax.linalg.triangular_solve(
        a + jnp.eye(chunk, dtype=a.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    v_own, k_cum = solved[..., :dv], solved[..., dv:]
    scores = dot("nbhik,nbhjk->nbhij", q, k) * decay
    q_in = q * jnp.exp(gc)[..., None]                # reads the old state
    g_end = gc[..., -1]
    k_out = k * jnp.exp(g_end[..., None] - gc)[..., None]   # writes to the end

    def step(state, xs):
        v_own_c, k_cum_c, scores_c, q_in_c, k_out_c, g_end_c = xs
        u = v_own_c - dot("bhik,bhkv->bhiv", k_cum_c, state)
        out = dot("bhik,bhkv->bhiv", q_in_c, state) \
            + dot("bhij,bhjv->bhiv", scores_c, u)
        state = state * jnp.exp(g_end_c)[..., None, None] \
            + dot("bhik,bhiv->bhkv", k_out_c, u)
        return state, out

    _, out = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32),
                      (v_own, k_cum, scores, q_in, k_out, g_end))
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 3, 2)   # (B, N, C, H, Dv)
    return out.reshape(b, n * chunk, h, dv)[:, :s]
