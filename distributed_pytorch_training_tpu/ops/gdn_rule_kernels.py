"""The chunked gated delta rule as a pair of Pallas TPU kernels with the
rule's own backward (`gdn_rule_fwd`, `gdn_rule_bwd`): what
`ops/gated_delta_rule.py` runs in a one-device program on a TPU, for the
model (`gated_delta_mixer`) and for a caller with the rule's own operands
(`gated_delta_rule`).

One grid step is one chunk of CHUNK positions of eight heads. The chunk axis
is the innermost, sequential one; each head's (key, value) state lives in
VMEM scratch across it, zeroed at chunk 0. Everything a chunk needs besides
that state (the in-chunk decays, ``A``, the inverse of ``I + A``, the scores)
is made in VMEM from the chunk's rows of q, k, v, g, beta: nothing per chunk
goes to HBM but the output rows and the backward's two residuals.

q, k, v are read as the mixer holds them (since PR 39 literally): the
convolution's output, ``(B, S, q | k | v columns)`` in the model's dtype
with q and k at the KEY heads' count, is handed over three times and each
operand's block found in it by column (a step's eight value heads read the
four key heads that serve them: 64 kB of bf16 a chunk for q, the same for
k). The PROLOGUE does in VMEM what the mixer did in HBM: cast up, scale each
head's rows to unit length (``x * rsqrt(sum(x x) + 1e-6)``), scale q by
``Dk ** -0.5``, and hand a PACK of two value heads the one key head both
read (value head j reads key head j // 2: the repeat is the kernel's own
layout, no copy). The EPILOGUE applies the mixer's gated norm to a chunk's
rows before they leave, ``o * rsqrt(mean(o o) + eps) * w * silu(z)`` with z a
block of the in-projection's own columns, and writes what ``out_proj``
reads, in the model's dtype. The backward undoes both in VMEM: the gated
norm's backward from the output's cotangent (dz, and the weight's cotangent
summed over chunks in an output block that stays while they pass), the sum
over the value heads a key head served, the scaling's backward, and dq, dk
written once, at the key heads' width, in the operand's dtype. No float32
table of q, k or o, repeated or not, is in HBM on this path (at 8,192 x 32
heads of 128 the mixer's own lines moved ~2.3 GB a layer for them). What the
backward needs of o it makes again (``[k; q_in] S`` as the forward stacks
it, and ``scores u``: one product more of twenty-one) rather than keep 134
MB a layer; PERF.md, PR 39, has both measured. Only g and beta, a megabyte
each, are regrouped outside, to ``(B, H / heads, S, heads)``, so that a
step's block of them is whole in its last dimension.

The mixer's entry (`gated_delta_mixer_kernels`, one `custom_vjp`) starts a
step earlier, at the in-projection's output q | k | v | z as it stands: the
convolution + SiLU of `gdn_conv_kernels` writes the one table these kernels
read q, k and v from, and z is found in the projection's output by column
(its first block lies ``conv_dim`` columns in), so no slice of the
projection exists. Its backward writes the projection's cotangent once, by
two kernels into one array: `gdn_rule_bwd` puts dz where z's columns are in
an array of the projection's width, `gdn_conv_bwd` takes that array as its
output (aliased), reads dq, dk and dv as they were left (three tables: it
finds a column block in the one that holds it) and fills q | k | v's columns
beside dz.

A caller with the rule's own operands (float32 or not, a head each:
`gated_delta_rule_kernels`, which the benchmark's rule check reaches) runs
the SAME two bodies: what the operands are is a static description
(`_Form`), and where it says they are raw, prologue and epilogue are not
traced. So the check holds the recurrence the model's step runs.

The mathematics is `gated_delta_rule._chunked_rule`'s (its docstring has the
derivation), in another order of summation::

    gc = cumsum(g); Gamma[i, j] = exp(gc_i - gc_j) for j <= i
    A = strictly_lower(beta_i (k_i . k_j) Gamma[i, j]);  T = (I + A)^-1
    u = T (beta * (v - exp(gc) * (k S)))
    o = (exp(gc) * q) S + ((q k^T) * Gamma) u
    S' = exp(gc_end) S + (k * exp(gc_end - gc))^T u

What a product costs here is less its arithmetic than its being one: at
HIGHEST a float32 product is six bf16 passes over three loads of its right
side, whatever part of the 128 x 128 unit a 64-wide tile fills. So products
that share a right side are stacked into one (``[k_beta; q] k^T``,
``[k; q_in] S``), and the heads go in PACKs of two whose (CHUNK, CHUNK) tables
lie side by side in one (CHUNK, 128) array: a product of two tables is then
one product for both heads, its right side laid out block-diagonally (the
ten of the inverse are most of a chunk's products). And since those ten wait
on each other, the four packs of a grid step are advanced in turn, one
product each (`_in_turn`), so that independent products stand side by side.

The solve: Mosaic lowers no `triangular_solve`, so ``T`` is formed, by block
forward substitution written as products. ``I + A`` restricted to its 2 x 2
diagonal blocks has the exact inverse ``I - A_2``; and if ``T_b`` inverts the
b x b diagonal blocks, with ``off`` the part of ``A`` that joins the two
halves of each 2b x 2b block, then ``I + A_2b = (I + A_b)(I + T_b off)`` and
``(T_b off)^2 = 0`` (it maps first halves to second halves only), so
``T_2b = T_b - T_b off T_b`` exactly. Five doublings reach 64: ten products
a chunk. It is exact in exact arithmetic, and in floating point it is
substitution, not a power series: the Neumann product ``(I - A)(I + A^2) ...
(I + A^32)`` costs the same ten products but passes through ``A^n``, whose
entries reach binomial(63, n) when the keys of a chunk repeat (a run of one
token) and cancel catastrophically.

The backward runs the chunks in reverse with the state's cotangent in VMEM
scratch, recomputes a chunk's other intermediates, and gives gradients for
all its inputs. Residuals besides the inputs: the state each chunk STARTED
from, ``(B, S / CHUNK, H, Dk, Dv)`` float32 (268 MB a layer at 8,192 x 32
heads of 128 x 128), and each chunk's ``T``, packed, ``(B, S / CHUNK, H /
PACK, CHUNK, PACK * CHUNK)`` (67 MB): a quarter of the states for ten of the
backward's thirty products. They are outputs of the differentiated forward
only; a forward that nothing differentiates (evaluation, the benchmark's
rule check) is the same kernel without the two outputs and puts neither in
HBM. The differentiated forward names its three outputs
(`RESIDUAL_NAMES`, `jax.ad_checkpoint.checkpoint_name`): under a
`jax.checkpoint` whose policy saves those names (the hybrid layer's,
models/qwen3_next.py) the first pass's outputs are the ones the backward
reads and the kernel runs once a layer; a `pallas_call` cannot lose one
output, so all three are kept or the call is made again. Under no policy,
or under one that does not list them, a name is an identity.

Where it runs (`gated_delta_mixer` and `gated_delta_rule` ask the three
gates below): on a TPU, for heads in whole PACKs of sizes in whole 128-lane
tiles (and, for the mixer's tables, two value heads a key head or one), in a
program that is one device's. A multi-device GSPMD program takes the XLA
form, as it did before there were kernels: GSPMD cannot partition a Mosaic
kernel, and no mesh reaches the rule to `shard_map` it over.

Precision: every product is float32 at HIGHEST; the state, the decays,
``T``, both norms and the gate are float32. Inputs are cast up on the chip.
Nothing is rounded that the XLA lines around the rule do not round: q, k, v
and z enter as the model's dtype they already are, the mixer's output is
rounded to it once (where ``o.astype(dtype)`` stood), and a cotangent is
rounded once to its operand's dtype, dq and dk after the l2 norm's backward
as XLA's transpose of the cast does. Raw operands' output stays float32.
"""

from __future__ import annotations

import functools
import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gdn_conv_kernels import (
    conv_silu_backward, conv_silu_forward, gdn_conv_supports,
)

# what the differentiated forward's outputs are called, for a remat policy
# that keeps them (`save_only_these_names`): the output, the chunk-start
# states, the packs' inverses
RESIDUAL_NAMES = ("gdn_rule_out", "gdn_rule_starts", "gdn_rule_inverses")

CHUNK = 64              # the kernels' own: ten 64-wide products invert I + A
HEADS_PER_STEP = 8      # at most: four packs in turn a step; 16 measured the same
PACK = 128 // CHUNK     # heads whose (CHUNK, CHUNK) tables share 128 lanes
L2_EPSILON = 1e-6       # the mixer's, under the root that scales q and k

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def gdn_rule_backend_supported() -> bool:
    """The backend's gate of the kernel path (`flash_backend_supported` and
    `paged_attention_backend_supported` are its siblings): a TPU. On the CPU
    the kernels run in interpreter mode, for the tests only."""
    return jax.default_backend() == "tpu"


def gdn_rule_supports(heads: int, dk: int, dv: int,
                      key_heads: Optional[int] = None) -> bool:
    """The shapes the kernels are written for and were compiled for on the
    chip: heads in whole PACKs, and head sizes of whole 128-lane tiles (a
    step's block of q, k or v is then lane-aligned whatever the group). The
    interpreter has no tiles and takes any head size. With ``key_heads``,
    the mixer's own tables besides: a key head serves a PACK's heads or one
    (two value heads a key head as published, or one: a ratio the PACKs
    cannot hold has no shared rows to hand a pack), and v's columns start at
    a whole block of a step's value heads in the convolution's output (z's,
    a whole count of such blocks further in the in-projection's, then do
    too)."""
    if heads % PACK or not (_interpret()
                            or (dk % 128 == 0 and dv % 128 == 0)):
        return False
    if key_heads is None:
        return True
    return (heads % key_heads == 0 and PACK % (heads // key_heads) == 0
            and 2 * key_heads * dk % (_heads_per_step(heads) * dv) == 0)


def gdn_rule_one_device_trace() -> bool:
    """Whether what is being traced is one device's program. GSPMD cannot
    partition a Mosaic kernel (a lowering error on any multi-device program:
    `make_flash_attention_fn` has the story), and the rule, unlike
    attention, is handed no mesh to `shard_map` itself over. Inside a
    `shard_map` whose axes of any size are all manual the operands are one
    shard's, and that is seen here. Outside one, how many devices a jitted
    program spans is decided after the trace, by its operands' shardings:
    all a trace can know is whether the process has more than one."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.manual_axes:
        return all(size == 1 for axis, size in mesh.shape.items()
                   if axis not in mesh.manual_axes)
    return jax.device_count() == 1


def _dot(x, y, dims):
    return lax.dot_general(x, y, (dims, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _total(x):   # (1, 1): the sum of a 2-D array
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _stack(parts):
    return jnp.concatenate(parts, axis=0)


# A PACK of heads shares every (CHUNK, CHUNK) table: head h of the pack owns
# lanes [h * CHUNK, (h + 1) * CHUNK) of one (CHUNK, PACK * CHUNK) array, so
# that a table fills the 128 lanes of a vector register and of the matrix
# unit, and a product of two tables is ONE product for the pack: the left
# side as it is, the right side laid out block-diagonally.

def _lanes(parts):
    return jnp.concatenate(parts, axis=1)


def _diagonal(parts):
    """(p * rows, p * d): ``parts[h]`` (rows, d) as the h-th diagonal block
    of zeros elsewhere."""
    zero = jnp.zeros_like(parts[0])
    return _stack([_lanes([part if i == h else zero
                           for i in range(len(parts))])
                   for h, part in enumerate(parts)])


def _diagonal_blocks(x, p):
    """The p diagonal blocks of ``x`` (p * rows, p * d)."""
    rows, d = x.shape[0] // p, x.shape[1] // p
    return [x[h * rows:(h + 1) * rows, h * d:(h + 1) * d] for h in range(p)]


def _split(x, p):
    d = x.shape[1] // p
    return [x[:, h * d:(h + 1) * d] for h in range(p)]


class _Pack:
    """One chunk of a pack of heads: first what needs no state (the decay
    table, ``A`` and its inverse, the scores, as packed tables), then, given
    each head's ``k S``, the chunk's ``u``. The forward reads its output
    from these; the backward recomputes them, all but the inverse ``t``,
    which it is handed. Per-head quantities are lists over the pack.

    A pack's products wait on each other (the inverse alone is a chain of
    ten), those of another pack of the same grid step do not. So the work
    is written as generators that ``yield`` wherever a product's result is
    awaited, and `_in_turn` advances the packs of a step one product each:
    in program order independent products then stand side by side, and the
    matrix units overlap them (by that order alone the forward went from
    8.6 to 6.5 ms at two packs a step, and to 5.8 at four)."""

    def __init__(self, qs, ks, vs, gs, gcs, afters, betas):
        c, p = CHUNK, len(qs)
        self.p, self.qs, self.ks, self.vs, self.betas = p, qs, ks, vs, betas
        self.gs = gs
        row = lax.broadcasted_iota(jnp.int32, (c, p * c), 0)
        lane = lax.broadcasted_iota(jnp.int32, (c, p * c), 1)
        col, self.owner = lane & (c - 1), lane >> (c.bit_length() - 1)
        self.row, self.col = row, col
        self.lower, self.strict = row >= col, row > col
        self.k_betas = [k * beta for k, beta in zip(ks, betas)]
        self.intos = [jnp.exp(gc) for gc in gcs]     # read the old state
        self.q_ins = [q * into for q, into in zip(qs, self.intos)]
        self.to_ends = [jnp.exp(after) for after in afters]   # write to the
        self.k_outs = [k * e for k, e in zip(ks, self.to_ends)]   # chunk end
        last = lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
        self.keeps = [                               # (1, 1): a state's decay
            jnp.exp(jnp.sum(jnp.where(last, gc, 0.0), axis=0, keepdims=True))
            for gc in gcs]

    def tables(self, t=None):
        """The decay table, ``A``, the scores and ``t``: the inverse of
        ``I + A`` by the doubling of the module's docstring, unless given."""
        c = CHUNK
        # the decay table's exponent gc_i - gc_j = sum of g over (j, i],
        # summed as such: [m <= i] times g_m [m > j]. As a difference of two
        # running sums it would lose, under strong decay (gc near -1e3 by
        # the chunk's end), the digits that tell neighbours apart. Zero on
        # and above the diagonal by construction, so nothing overflows.
        table = _dot(self.lower[:, :c].astype(jnp.float32),
                     jnp.where(self.strict, self.spread(self.gs), 0.0), _NN)
        yield
        self.decay = jnp.where(self.lower, jnp.exp(table), 0.0)
        # [k_beta; q] k^T: A's and the scores' products share their right side
        both = _dot(_stack([_lanes(self.k_betas), _lanes(self.qs)]),
                    _diagonal(self.ks), _NT)
        yield
        self.a = jnp.where(self.strict, both[:c] * self.decay, 0.0)
        self.scores = both[c:] * self.decay
        if t is not None:
            self.t = t
            return

        def same(log2_block):
            return (self.row >> log2_block) == (self.col >> log2_block)

        t = (self.row == self.col).astype(jnp.float32) \
            - jnp.where(same(1), self.a, 0.0)
        for log2_block in range(1, c.bit_length() - 1):
            off = jnp.where(same(log2_block + 1) & ~same(log2_block),
                            self.a, 0.0)
            inner = self.times(off, t)
            yield
            t = t - self.times(t, inner)
            yield
        self.t = t

    def spread(self, columns):
        """(CHUNK, p * CHUNK): head h's (CHUNK, 1) column along its lanes."""
        out = jnp.broadcast_to(columns[0], self.owner.shape)
        for h in range(1, self.p):
            out = jnp.where(self.owner == h, columns[h], out)
        return out

    def own(self, table, h):
        """``table`` with the lanes of the pack's other heads zeroed."""
        return jnp.where(self.owner == h, table, 0.0)

    def times(self, x, y):
        """Head by head ``x_h y_h`` of two packed tables, as one product."""
        return _dot(x, _stack([self.own(y, h) for h in range(self.p)]), _NN)

    def solve(self, helds):
        """``helds[h]`` = k_h S_h, what the incoming state already holds."""
        self.helds = helds
        self.missings = [v - into * held for v, into, held
                         in zip(self.vs, self.intos, helds)]
        self.us = _split(_dot(self.t, _diagonal(
            [beta * m for beta, m in zip(self.betas, self.missings)]), _NN),
            self.p)

    def outputs(self, states):
        """The chunk's ``u`` and then ``outs``, the rule's output rows a
        head: [k; q_in] S is what the state holds of the keys and what it
        answers the queries, ``scores u`` what the chunk itself answers."""
        c = CHUNK
        reads = [_dot(_stack([k, q_in]), state, _NN)
                 for k, q_in, state in zip(self.ks, self.q_ins, states)]
        yield
        self.solve([read[:c] for read in reads])
        yield
        answers = _split(_dot(self.scores, _diagonal(self.us), _NN), self.p)
        self.outs = [read[c:] + answer
                     for read, answer in zip(reads, answers)]


def _in_turn(steps):
    """Advances the generators one ``yield`` each, round after round."""
    for _ in itertools.zip_longest(*steps):
        pass


def _running_sums(g):
    """For a (CHUNK, heads) block of g: ``gc`` = the sum up to and including
    each row, and ``after`` = the sum of the rows after it, each as one
    product with a 0/1 triangle (exact at HIGHEST)."""
    c = CHUNK
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    g = g.astype(jnp.float32)
    return (_dot((row >= col).astype(jnp.float32), g, _NN),
            _dot((row < col).astype(jnp.float32), g, _NN))


def _with_column(block, h, column):
    """``block`` (CHUNK, heads) with its column ``h`` set to ``column``."""
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.where(lane == h, column, block)


def _packs(heads: int):
    """The heads of a grid step, PACK at a time."""
    return [range(first, first + PACK) for first in range(0, heads, PACK)]


class _Form(NamedTuple):
    """What a call's operands are, known when it is traced. With a gated
    norm's ``epsilon`` they are the mixer's own tables: q and k at
    ``key_heads`` heads in the convolution's dtype, to be scaled to unit
    length (and q by ``dk ** -0.5``) before the rule, each key head serving
    ``heads / key_heads`` value heads in a row, and the result goes through
    the gated norm before it leaves. With ``epsilon`` None q and k
    are the rule's operands as they stand, a head each, and the result is
    the rule's, float32: the same kernel bodies with prologue and epilogue
    left out where they are traced."""

    heads: int
    key_heads: int
    dk: int
    dv: int
    epsilon: Optional[float]

    @property
    def mixer(self) -> bool:
        return self.epsilon is not None

    @property
    def step(self) -> int:
        """Value heads a grid step takes."""
        return _heads_per_step(self.heads)

    @property
    def ratio(self) -> int:
        return self.heads // self.key_heads

    @property
    def conv_dim(self) -> int:
        """The mixer's q | k | v columns: those before z in the
        in-projection's output."""
        return 2 * self.key_heads * self.dk + self.heads * self.dv


class _Unit:
    """Rows scaled to unit length as the mixer does it, ``x * rsqrt(sum(x x)
    + 1e-6)``, and that scaling's backward."""

    def __init__(self, x):
        self.factor = lax.rsqrt(_rowsum(x * x) + L2_EPSILON)
        self.rows = x * self.factor

    def backward(self, d):
        """The cotangent of ``rows`` -> that of ``x``."""
        return self.factor * (d - self.rows * _rowsum(d * self.rows))


class _GatedNorm:
    """One head's rows of the mixer's output norm: ``y = o * rsqrt(mean(o o)
    + eps) * w * silu(z)``, float32 throughout; ``w`` is (1, Dv)."""

    def __init__(self, o, z, w, epsilon):
        self.z, self.w = z, w
        self.factor = lax.rsqrt(
            _rowsum(o * o) * (1.0 / o.shape[1]) + epsilon)
        self.normed = o * self.factor
        self.sigmoid = 1.0 / (1.0 + jnp.exp(-z))
        self.silu = z * self.sigmoid

    @property
    def out(self):
        return self.normed * self.w * self.silu

    def backward(self, dy):
        """The cotangent of ``out`` -> those of o, z and ``w`` (1, Dv)."""
        dnormed = dy * (self.w * self.silu)
        do = self.factor * (dnormed - self.normed * (
            _rowsum(dnormed * self.normed) * (1.0 / dy.shape[1])))
        through = dy * self.normed
        dz = through * self.w * (self.sigmoid * (
            1.0 + self.z * (1.0 - self.sigmoid)))
        return do, dz, jnp.sum(through * self.silu, axis=0, keepdims=True)


def _rows(ref, d, h):
    return ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)


def _pack(form, q_ref, k_ref, v_ref, g_ref, sums, beta_ref, members):
    """The chunk of the step's value heads ``members``. In the mixer's form
    a key head's rows are read and scaled once for the value heads it
    serves (a PACK of two shares one key head at the published ratio);
    ``units`` keeps, by key head, what the scaling's backward needs."""
    columns = lambda x: [x[:, h:h + 1].astype(jnp.float32)  # noqa: E731
                         for h in members]
    if form.mixer:
        keys = [h // form.ratio for h in members]
        units = {key: (_Unit(_rows(q_ref, form.dk, key)),
                       _Unit(_rows(k_ref, form.dk, key)))
                 for key in dict.fromkeys(keys)}
        scaled = {key: q.rows * form.dk ** -0.5
                  for key, (q, _) in units.items()}
        qs = [scaled[key] for key in keys]
        ks = [units[key][1].rows for key in keys]
    else:
        keys, units = list(members), None
        qs = [_rows(q_ref, form.dk, h) for h in members]
        ks = [_rows(k_ref, form.dk, h) for h in members]
    pk = _Pack(qs, ks, [_rows(v_ref, form.dv, h) for h in members],
               columns(g_ref[0, 0]), columns(sums[0]), columns(sums[1]),
               columns(beta_ref[0, 0]))
    pk.keys, pk.units = keys, units
    return pk


def _fwd_kernel(*refs, form: _Form):
    """Operands: q, k, v, g, beta and, in the mixer's form, z and the norm's
    weight; then the output, the backward's two residuals where they are
    asked for (each chunk's starting states, its packs' inverses), and the
    state scratch."""
    q_ref, k_ref, v_ref, g_ref, beta_ref, *rest = refs
    if form.mixer:
        z_ref, w_ref, *rest = rest
    o_ref, *residuals, state_scr = rest

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state_scr[...] = jnp.zeros_like(state_scr)

    dv = form.dv
    sums = _running_sums(g_ref[0, 0])

    def forward(n, members):
        pk = _pack(form, q_ref, k_ref, v_ref, g_ref, sums, beta_ref, members)
        yield from pk.tables()
        states = [state_scr[h] for h in members]
        yield from pk.outputs(states)
        if residuals:
            start_ref, t_ref = residuals
            t_ref[0, 0, n] = pk.t
            for i, h in enumerate(members):
                start_ref[0, 0, h] = states[i]
        for i, h in enumerate(members):
            o = pk.outs[i]
            if form.mixer:
                o = _GatedNorm(o, _rows(z_ref, dv, h), w_ref[...],
                               form.epsilon).out
            o_ref[0, :, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
            state_scr[h] = states[i] * pk.keeps[i] + _dot(
                pk.k_outs[i], pk.us[i], _TN)

    _in_turn([forward(n, members)
              for n, members in enumerate(_packs(form.step))])


def _bwd_kernel(*refs, form: _Form):
    """Operands: the forward's, its two residuals and the output's
    cotangent; then the cotangents of q, k, v, g, beta and, in the mixer's
    form, of z and (summed over the step's heads and the chunks so far) of
    the norm's weight; then the scratch. Chunks arrive last first;
    ``dstate_scr`` is the cotangent of the state the chunk LEAVES, zero
    behind the last chunk."""
    q_ref, k_ref, v_ref, g_ref, beta_ref, *rest = refs
    if form.mixer:
        z_ref, w_ref, *rest = rest
    start_ref, t_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, \
        *rest = rest
    if form.mixer:
        dz_ref, dw_ref, *rest = rest
    dstate_scr, = rest

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)
        if form.mixer:
            dw_ref[...] = jnp.zeros_like(dw_ref)

    c, dk, dv = CHUNK, form.dk, form.dv
    heads = form.step
    sums = _running_sums(g_ref[0, 0])
    last = lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    dgcs, dbetas = {}, {}       # head of the step -> its (CHUNK, 1) column
    dws = []                    # (1, Dv) a head

    def backward(n, members):
        pk = _pack(form, q_ref, k_ref, v_ref, g_ref, sums, beta_ref, members)
        p = pk.p
        yield from pk.tables(t_ref[0, 0, n])
        states = [start_ref[0, 0, h] for h in members]
        dstates = [dstate_scr[h] for h in members]
        dos = [_rows(do_ref, dv, h) for h in members]
        if form.mixer:
            # the rule's output once more, as the forward made it: the
            # gated norm's backward needs it, and it is a product of
            # twenty-one where keeping it is 134 MB a layer
            yield from pk.outputs(states)
            yield
            for i, h in enumerate(members):
                norm = _GatedNorm(pk.outs[i], _rows(z_ref, dv, h),
                                  w_ref[...], form.epsilon)
                dos[i], dz, dw = norm.backward(dos[i])
                dz_ref[0, :, h * dv:(h + 1) * dv] = dz.astype(dz_ref.dtype)
                dws.append(dw)
        else:
            helds = [_dot(k, state, _NN) for k, state in zip(pk.ks, states)]
            yield
            pk.solve(helds)
        # o = q_in S + scores u;  S' = keep S + k_out^T u. A packed table's
        # transpose times the pack's rows gives every pairing of heads; each
        # head's own is a diagonal block.
        from_o = _diagonal_blocks(_dot(pk.scores, _lanes(dos), _TN), p)
        from_state = [_dot(k_out, dstate, _NN)
                      for k_out, dstate in zip(pk.k_outs, dstates)]
        yield
        dus = [x + y for x, y in zip(from_o, from_state)]
        dk_outs = [_dot(u, dstate, _NT) for u, dstate in zip(pk.us, dstates)]
        # u = T r, T = (I + A)^-1:  dr = T^T du,  dA = -dr u^T;
        # dscores = do u^T shares the right side
        drs = _diagonal_blocks(_dot(pk.t, _lanes(dus), _TN), p)
        yield
        tables = _dot(_stack([_lanes(dos), _lanes(drs)]), _diagonal(pk.us),
                      _NT)
        # r = beta * (v - into * held)
        dmissings = [beta * dr for beta, dr in zip(pk.betas, drs)]
        dhelds = [-into * dm for into, dm in zip(pk.intos, dmissings)]
        # [do; dheld] S^T: q_in's and k's cotangents through the old state
        through = [_dot(_stack([do, dheld]), state, _NT)
                   for do, dheld, state in zip(dos, dhelds, states)]
        yield
        dscores = jnp.where(pk.lower, tables[:c], 0.0)
        da = jnp.where(pk.strict, -tables[c:], 0.0)
        # A = (k_beta k^T) * decay;  scores = (q k^T) * decay
        dproducts = _stack([da * pk.decay, dscores * pk.decay])
        right = _split(_dot(dproducts, _diagonal(pk.ks), _NN), p)
        left = _diagonal_blocks(_dot(dproducts, _stack(
            [_lanes(pk.k_betas), _lanes(pk.qs)]), _TN), p)
        yield
        dtable = da * pk.a + dscores * pk.scores     # d(gc_i - gc_j)
        dtable_columns = _rowsum(dtable.T)           # (p * CHUNK, 1)
        dqs, dks = [], []       # of the rule's own q and k, a value head each
        for i, h in enumerate(members):
            q, k, beta, state = pk.qs[i], pk.ks[i], pk.betas[i], states[i]
            dk_beta, dk_out, dq_in = right[i][:c], dk_outs[i], through[i][:c]
            dqs.append(right[i][c:] + pk.intos[i] * dq_in)
            dks.append(through[i][c:] + dk_beta * beta + left[i]
                       + pk.to_ends[i] * dk_out)
            dv_ref[0, :, h * dv:(h + 1) * dv] = dmissings[i].astype(
                dv_ref.dtype)
            dbetas[h] = _rowsum(drs[i] * pk.missings[i]) \
                + _rowsum(dk_beta * k)
            # the decays: gc through into, to_end, keep and the table
            dinto = _rowsum(dq_in * q) - _rowsum(
                dmissings[i] * pk.helds[i])
            dto_end = _rowsum(dk_out * k) * pk.to_ends[i]
            dg_end = (pk.keeps[i] * _total(state * dstates[i])
                      + _total(dto_end))
            dgcs[h] = (dinto * pk.intos[i] - dto_end
                       + _rowsum(pk.own(dtable, i))
                       - dtable_columns[i * c:(i + 1) * c]
                       + jnp.where(last, dg_end, 0.0))
            dstate_scr[h] = dstates[i] * pk.keeps[i] + _dot(
                _stack([pk.q_ins[i], k]), _stack([dos[i], dhelds[i]]), _TN)
        if form.mixer:
            # a key head's cotangent is the sum over the value heads it
            # served, sent back through the scaling and written once
            for key, (q_unit, k_unit) in pk.units.items():
                served = [i for i in range(p) if pk.keys[i] == key]
                dq_ref[0, :, key * dk:(key + 1) * dk] = q_unit.backward(
                    sum(dqs[i] for i in served) * dk ** -0.5).astype(
                        dq_ref.dtype)
                dk_ref[0, :, key * dk:(key + 1) * dk] = k_unit.backward(
                    sum(dks[i] for i in served)).astype(dk_ref.dtype)
        else:
            for i, h in enumerate(members):
                dq_ref[0, :, h * dk:(h + 1) * dk] = dqs[i].astype(
                    dq_ref.dtype)
                dk_ref[0, :, h * dk:(h + 1) * dk] = dks[i].astype(
                    dk_ref.dtype)

    _in_turn([backward(n, members)
              for n, members in enumerate(_packs(heads))])
    dgc_all = jnp.zeros((c, heads), jnp.float32)
    dbeta_all = jnp.zeros((c, heads), jnp.float32)
    for h in range(heads):
        dgc_all = _with_column(dgc_all, h, dgcs[h])
        dbeta_all = _with_column(dbeta_all, h, dbetas[h])
    # g -> gc is a running sum: its transpose sums from the end
    upper = (lax.broadcasted_iota(jnp.int32, (c, c), 0)
             <= lax.broadcasted_iota(jnp.int32, (c, c), 1))
    dg_ref[0, 0] = _dot(upper.astype(jnp.float32), dgc_all,
                        _NN).astype(dg_ref.dtype)
    dbeta_ref[0, 0] = dbeta_all.astype(dbeta_ref.dtype)
    if form.mixer:
        dw_ref[0, 0] += sum(dws)


def _heads_per_step(h: int) -> int:
    """The most whole PACKs, up to HEADS_PER_STEP heads, that divide ``h``."""
    return max(d for d in range(PACK, HEADS_PER_STEP + 1, PACK) if h % d == 0)


def _grouped(x, hb):   # (B, S, H) -> (B, H / hb, S, hb)
    b, s, h = x.shape
    return jnp.moveaxis(x.reshape(b, s, h // hb, hb), 2, 1)


def _ungrouped(x):     # back
    b, groups, s, hb = x.shape
    return jnp.moveaxis(x, 1, 2).reshape(b, s, groups * hb)


class _Specs:
    """The blocks of one call: ``q``, ``k``, ``v`` of the operands (in the
    mixer's form three ranges of columns of ONE array, the convolution's
    output, each found by the index of its first block, and ``z`` the
    columns behind them in the in-projection's), ``key`` and ``wide`` of
    tables that hold nothing else, a step's key heads or value heads wide."""

    def __init__(self, form: _Form, n: int, *, reverse: bool):
        chunk_of = (lambda c: n - 1 - c) if reverse else (lambda c: c)
        hb, dk, dv = form.step, form.dk, form.dv
        kb = hb // form.ratio

        def columns(width, first=0):
            return pl.BlockSpec((1, CHUNK, width),
                                lambda i, j, c: (i, chunk_of(c), first + j))

        self.key, self.wide = columns(kb * dk), columns(hb * dv)
        self.q, self.k, self.v = self.key, self.key, self.wide
        self.weight = pl.BlockSpec((1, dv), lambda i, j, c: (0, 0))
        self.gate = []
        if form.mixer:
            self.k = columns(kb * dk, form.key_heads // kb)
            self.v = columns(hb * dv, 2 * form.key_heads * dk // (hb * dv))
            # z, and its cotangent: the in-projection's columns behind
            # q | k | v
            self.z = columns(hb * dv, form.conv_dim // (hb * dv))
            self.gate = [self.z, self.weight]
        self.narrow = pl.BlockSpec(
            (1, 1, CHUNK, hb), lambda i, j, c: (i, j, chunk_of(c), 0))
        self.start = pl.BlockSpec(
            (1, 1, hb, dk, dv), lambda i, j, c: (i, chunk_of(c), j, 0, 0))
        self.inverse = pl.BlockSpec(
            (1, 1, hb // PACK, CHUNK, PACK * CHUNK),
            lambda i, j, c: (i, chunk_of(c), j, 0, 0))
        # a step's sum over its chunks: the block stays while they pass
        self.dweight = pl.BlockSpec((1, 1, 1, dv),
                                    lambda i, j, c: (i, j, 0, 0))


# four packs' tables live at once, beside double-buffered blocks of eight
# heads: with float32 v the backward is past the 16 MiB a kernel gets by
# default (compiled for a described v5e it fits in 20), and 32 is within
# the VMEM of every TPU that Mosaic compiles for
_SEQUENTIAL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 * 1024 * 1024)


# `jit(inline=True)` on the two calls: a kernel body, unrolled over four packs,
# is ~2,000 operations, and a step calls the rule nine times (three layers,
# their remat, their backward). jit keeps the traced call by its shapes, so
# the body is traced once a process; `inline` puts its equations into the
# caller at each site, under the caller's scope path (a jitted call proper
# is lowered once for all sites and its operations lose the path the
# region metrics read). Tracing is paid by every process, compile cache or
# not: without this `setup_s` rose by 9 s.
@functools.partial(jax.jit, inline=True, static_argnames=("form", "residuals"))
def _forward(q, k, v, g, beta, gate, *, form: _Form, residuals: bool):
    """(the output,) and, with ``residuals``, what the backward needs besides
    the inputs: each chunk's starting states and its packs' inverses. q, k,
    v: the (B, S, columns) arrays that hold them; ``gate``: (z, the norm's
    weight as a float32 row) in the mixer's form, whose output is q's dtype,
    else () and float32."""
    b, s, h = g.shape
    dk, dv, hb, n = form.dk, form.dv, form.step, s // CHUNK
    sp = _Specs(form, n, reverse=False)
    kept = residuals * [
        (sp.start, jax.ShapeDtypeStruct((b, n, h, dk, dv), jnp.float32)),
        (sp.inverse, jax.ShapeDtypeStruct(
            (b, n, h // PACK, CHUNK, PACK * CHUNK), jnp.float32))]
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, form=form),
        name="gdn_rule_fwd", grid=(b, h // hb, n),
        in_specs=[sp.q, sp.k, sp.v, sp.narrow, sp.narrow, *sp.gate],
        out_specs=[sp.wide] + [spec for spec, _ in kept],
        out_shape=[jax.ShapeDtypeStruct(
            (b, s, h * dv), q.dtype if form.mixer else jnp.float32)]
        + [shape for _, shape in kept],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=_interpret())
    with jax.named_scope("gdn_rule_fwd"):
        return tuple(call(q, k, v, _grouped(g, hb), _grouped(beta, hb),
                          *gate))


@functools.partial(jax.jit, inline=True, static_argnames="form")
def _backward(q, k, v, g, beta, gate, starts, inverses, do, *, form: _Form):
    """The cotangents of q, k (a table of ``key_heads`` each), v, g, beta
    and, in the mixer's form, of z and of the norm's weight: z's in the
    columns z has in the in-projection's output, an array of that width
    whose other columns are NOT written (`conv_silu_backward` fills them);
    the weight's as a row for every group of heads, to be summed."""
    b, s, h = g.shape
    dk, dv, hb, n = form.dk, form.dv, form.step, s // CHUNK
    sp = _Specs(form, n, reverse=True)
    like = lambda x, *shape: jax.ShapeDtypeStruct(shape, x.dtype)  # noqa: E731
    narrow_shape = (b, h // hb, s, hb)
    gated = [(sp.z, like(gate[0], *gate[0].shape)),
             (sp.dweight, jax.ShapeDtypeStruct((b, h // hb, 1, dv),
                                               jnp.float32))
             ] if form.mixer else []
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, form=form),
        name="gdn_rule_bwd", grid=(b, h // hb, n),
        in_specs=[sp.q, sp.k, sp.v, sp.narrow, sp.narrow, *sp.gate, sp.start,
                  sp.inverse, sp.wide],
        out_specs=[sp.key, sp.key, sp.wide, sp.narrow, sp.narrow]
        + [spec for spec, _ in gated],
        out_shape=[like(q, b, s, form.key_heads * dk),
                   like(k, b, s, form.key_heads * dk), like(v, b, s, h * dv),
                   like(g, *narrow_shape), like(beta, *narrow_shape)]
        + [shape for _, shape in gated],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=_interpret())
    with jax.named_scope("gdn_rule_bwd"):
        dq, dk_, dv_, dg, dbeta, *dgate = call(
            q, k, v, _grouped(g, hb), _grouped(beta, hb), *gate, starts,
            inverses, do)
    return (dq, dk_, dv_, _ungrouped(dg), _ungrouped(dbeta), *dgate)


def _flat(x):   # (B, S, H, D) -> (B, S, H * D): a reshape, no copy
    return x.reshape(*x.shape[:2], -1)


def _raw_form(q, v) -> _Form:
    return _Form(q.shape[2], q.shape[2], q.shape[3], v.shape[3], None)


@jax.custom_vjp
def _rule(q, k, v, g, beta):
    out, = _forward(_flat(q), _flat(k), _flat(v), g, beta, (),
                    form=_raw_form(q, v), residuals=False)
    return out.reshape(v.shape)


def _named(outputs):
    """The differentiated forward's three outputs under `RESIDUAL_NAMES`."""
    return tuple(map(checkpoint_name, outputs, RESIDUAL_NAMES))


def _rule_fwd(q, k, v, g, beta):
    out, starts, inverses = _named(_forward(
        _flat(q), _flat(k), _flat(v), g, beta, (), form=_raw_form(q, v),
        residuals=True))
    return out.reshape(v.shape), (q, k, v, g, beta, starts, inverses)


def _rule_bwd(residuals, do):
    q, k, v, g, beta, starts, inverses = residuals
    dq, dk, dv, dg, dbeta = _backward(
        _flat(q), _flat(k), _flat(v), g, beta, (), starts, inverses,
        _flat(do), form=_raw_form(q, v))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg, dbeta)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _gate(qkvz, norm_w):
    """The rule's kernels' last two operands: z where it stands in the
    in-projection's output, the norm's weight as a float32 row."""
    return qkvz, norm_w.astype(jnp.float32).reshape(1, -1)


def _mixed(qkvz, taps, g, beta, norm_w, form, *, residuals: bool):
    """The convolution's kernel, then the rule's on its output and on z
    where it stands in ``qkvz``: (the convolution's output, the rule's
    outputs). The rule's part alone lies under scope ``gdn_rule``."""
    qkv = conv_silu_forward(qkvz, taps)
    with jax.named_scope("gdn_rule"):
        return qkv, _forward(qkv, qkv, qkv, g, beta, _gate(qkvz, norm_w),
                             form=form, residuals=residuals)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _mixer(qkvz, taps, g, beta, norm_w, form):
    return _mixed(qkvz, taps, g, beta, norm_w, form, residuals=False)[1][0]


def _mixer_fwd(qkvz, taps, g, beta, norm_w, form):
    qkv, outputs = _mixed(qkvz, taps, g, beta, norm_w, form, residuals=True)
    out, starts, inverses = _named(outputs)
    return out, (qkvz, taps, qkv, g, beta, norm_w, starts, inverses)


def _mixer_bwd(form, residuals, dout):
    """The in-projection's cotangent is written once, by two kernels into
    one array: the rule's backward leaves dz in z's columns, the
    convolution's reads dq, dk and dv where that left them and fills q | k
    | v's columns beside dz, in place."""
    qkvz, taps, qkv, g, beta, norm_w, starts, inverses = residuals
    with jax.named_scope("gdn_rule"):
        dq, dk, dv, dg, dbeta, dz, dweight = _backward(
            qkv, qkv, qkv, g, beta, _gate(qkvz, norm_w), starts, inverses,
            dout, form=form)
    dqkvz, dtaps = conv_silu_backward(qkvz, taps, (dq, dk, dv), dz)
    return (dqkvz, dtaps.astype(taps.dtype), dg, dbeta,
            dweight.sum(axis=(0, 1, 2)).astype(norm_w.dtype))


_mixer.defvjp(_mixer_fwd, _mixer_bwd)


def _padded(s, *tables):
    """The tables with their S axis filled to whole chunks by positions
    that leave the state alone (k = v = 0, beta = 0, g = 0)."""
    pad = -s % CHUNK
    if not pad:
        return tables
    return tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                 for x in tables)


def gated_delta_rule_kernels(q, k, v, g, beta):
    """`gated_delta_rule_stepwise`'s result by the two kernels. q, k: (B, S,
    H, Dk); v: (B, S, H, Dv); g, beta: (B, S, H); returns (B, S, H, Dv)
    float32. A length that is no multiple of CHUNK is padded with positions
    that leave the state alone (k = v = 0, beta = 0, g = 0). The heads come
    in PACKs: `gdn_rule_supports` says which shapes may be sent here."""
    s, h = q.shape[1:3]
    if h % PACK:
        raise ValueError(f"gated_delta_rule_kernels: {h} heads are not whole "
                         f"packs of {PACK}")
    return _rule(*_padded(s, q, k, v, g, beta))[:, :s]


def gated_delta_mixer_kernels(qkvz, taps, g, beta, norm_w, epsilon, *,
                              key_heads: int):
    """The mixer between its two projections, by two kernel pairs: the
    convolution's (`gdn_conv_kernels`) and the rule's between the tables
    that leaves. `gated_delta_rule.gated_delta_mixer` has the equations.
    qkvz: (B, S, 2 * key_heads * Dk + 2 * H * Dv), the in-projection's
    output, its columns q | k | v | z; taps: (K, 2 * key_heads * Dk + H *
    Dv); g, beta: (B, S, H) float32; norm_w: (Dv,). Returns (B, S, H * Dv)
    in qkvz's dtype. `gdn_rule_supports` with ``key_heads`` and
    `gdn_conv_supports` say which shapes may be sent here."""
    s, h = g.shape[1:]
    dv = norm_w.shape[-1]
    dk = (qkvz.shape[-1] - 2 * h * dv) // (2 * key_heads)
    if not (gdn_rule_supports(h, dk, dv, key_heads) and gdn_conv_supports(
            (key_heads * dk, key_heads * dk, h * dv), taps.shape[0])):
        raise ValueError(
            f"gated_delta_mixer_kernels: {h} value heads of {dv} over "
            f"{key_heads} key heads of {dk} under {taps.shape[0]} taps are "
            "no shape of the kernels'")
    form = _Form(h, key_heads, dk, dv, float(epsilon))
    return _mixer(*_padded(s, qkvz), taps, *_padded(s, g, beta), norm_w,
                  form)[:, :s]
