"""`fit_check_serve_lm` for a cell whose model generates by blocks: the same
compile of ``paged_decode`` and the largest bucket's ``paged_prefill`` for a
described v5e at each ``--rows``, over the engine such a model asks for
(`serving/block_diffusion.py::BlockDiffusionEngine`, whose ``paged_decode``
is the block step) in place of the `SlotEngine` that tool names.

    JAX_PLATFORMS=cpu python -m benchmark.tools.fit_check_block_diffusion \
        --workload serve_sdar_block_diffusion_batch --rows 160,176,192

Nothing runs; the rows are in PERF.md section 4. (That tool hands every model
the causal flash kernel for its prefill; this model's is the same kernel
under the block mask, the same tiles and the same memory.)
"""

from __future__ import annotations

import sys


def main(argv=None) -> int:
    from benchmark.tools import fit_check_serve_lm
    from distributed_pytorch_training_tpu.serving import (
        block_diffusion, continuous,
    )

    real = continuous.SlotEngine
    continuous.SlotEngine = block_diffusion.BlockDiffusionEngine
    try:
        return fit_check_serve_lm.main(argv)
    finally:
        continuous.SlotEngine = real


if __name__ == "__main__":
    sys.exit(main())
