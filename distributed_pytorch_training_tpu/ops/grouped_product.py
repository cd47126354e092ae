"""The grouped products of an expert layer whose rows are already sorted by
expert: ``out[r] = rows[r] @ weights[g]`` for every row ``r`` of group ``g``,
groups lying one after another from row 0, ``group_sizes[g]`` rows each
(`lax.ragged_dot`'s contract, rows past the last group unspecified).

A thin layer over the Pallas grouped matmul that jax ships
(`jax.experimental.pallas.ops.tpu.megablox`'s `gmm`): its grid walks (row tile,
group) visits in group-major order with the group offsets read by scalar
prefetch, a row tile being visited once for every group that crosses it;
products accumulate in float32 and are rounded once, at the store. What this
module adds is the tiling, worked out from the shapes so that ONE weight
tile spans the whole contraction and, where it fits, the whole output width:
the tile's block index then changes only when the group does, Pallas's
pipeline copies nothing for an index that stays, and an expert's weights
are read from HBM once while its rows pass, however many visits that takes.
On a v5e at the block-diffusion cell's shape (5,632 rows of 2,048 by 128 x
2,048 x 768 in bf16, ~44 rows a group) XLA's own `lax.ragged_dot` call reads
the 403 MB of weights at 31% of the HBM rate; PERF.md section 6 "PR 43" has
this form's times by tiling.

Who calls it: `models/moe.py::HeldExpertsMoe` where every expert is held
(one pass over the whole sorted order, forward only). The package's public
`gmm` wraps this kernel in a `custom_vjp` with transposes of its own; nothing
here drives or checks them, so this module calls the kernel itself (less to
trace at each of a step's eighteen call sites) and a caller that
differentiates keeps `lax.ragged_dot`.

What an instance costs besides: every distinct (rows, k, n) is a trace of
megablox's visit tables and kernel body and a Mosaic lowering in every
process that builds the program, cache or not, ~0.26 s of `setup_s` on the
chip's host (my chip run, PR 43). Hence rows in whole ROW_TILEs only: the 8
positions a model is initialised on are not worth an instance.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

# rows a visit multiplies: a visit costs a full tile's product whatever part
# of it the group holds, so the tile is the smallest the MXU fills. At the
# cell's shape a call takes 0.72 ms at 64, 0.70 at 128, 0.76 at 256, 1.22 at
# 512, and 0.88 with the contraction cut in two (my chip run, PR 43)
ROW_TILE = 128
# the largest weight tile, in bytes: two of them (the pipeline's double
# buffer) and the tiles of rows, result and accumulator stay inside the 16
# MB of VMEM a kernel may use on a v5e without asking for more
WEIGHT_TILE_BYTES = 4 * 2 ** 20


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def grouped_product_backend_supported() -> bool:
    """The backend's gate of the kernel (`flash_backend_supported` and
    `paged_attention_backend_supported` are its siblings): a TPU, and ONE
    device in the process, since GSPMD cannot partition a Mosaic kernel and
    the layer is handed no mesh to `shard_map` itself over
    (`models/sdar.py::_default_attention_fn` follows the same rule). On the
    CPU the kernel runs in interpreter mode, for the tests only."""
    return jax.default_backend() == "tpu" and jax.device_count() == 1


def grouped_product_tiling(rows: int, k: int, n: int, dtype
                           ) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` for ``(rows, k) @ (groups, k, n)``, or None where
    the shapes are not whole tiles: ``rows`` in whole ROW_TILEs, ``tk`` all
    of ``k``, ``tn`` the largest divisor of ``n`` in whole 128-lane tiles
    whose (k, tn) weight tile stays under WEIGHT_TILE_BYTES. bf16 or float32
    operands, as the kernel takes. The interpreter has no tiles and takes
    any shape, its row tile the largest power of two that divides
    ``rows``."""
    if jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    tm = ROW_TILE
    if _interpret():
        while rows % tm:
            tm //= 2
        return tm, k, n
    if rows % tm or k % 128 or n % 128:
        return None
    fits = [tn for tn in range(128, n + 1, 128)
            if n % tn == 0
            and k * tn * jnp.dtype(dtype).itemsize <= WEIGHT_TILE_BYTES]
    return (tm, k, fits[-1]) if fits else None


def grouped_product_supports(rows: int, k: int, n: int, dtype) -> bool:
    """Whether the kernel takes this product (`grouped_product_tiling`)."""
    return grouped_product_tiling(rows, k, n, dtype) is not None


def grouped_product(rows: jnp.ndarray, weights: jnp.ndarray,
                    group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``(m, k)`` sorted rows by ``(groups, k, n)`` weights -> ``(m, n)`` in
    the rows' dtype, group ``g`` being rows ``sum(group_sizes[:g]) ..`` of
    ``group_sizes[g]`` (int32; 0 and all of ``m`` included). Rows past the
    last group hold whatever was there: mask them, as after
    `lax.ragged_dot` on a TPU."""
    tiling = grouped_product_tiling(rows.shape[0], *weights.shape[1:],
                                    rows.dtype)
    if tiling is None:
        raise ValueError(
            f"no tiling for {rows.shape} {rows.dtype} by {weights.shape}: "
            "ask grouped_product_supports first")
    return gmm(rows, weights, group_sizes,
               preferred_element_type=rows.dtype, tiling=tiling,
               interpret=_interpret())
