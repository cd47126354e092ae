"""The named regions of a hybrid linear-attention expert model's train step
(`models/qwen3_next.py`): one tuple beside `_regions.TRAIN_STEP`, read by the
same `_regions.read`. ``gdn_rule`` lies inside ``gdn``, and the innermost
region counts an operation, so ``gdn`` alone is the mixer outside its rule;
the flash kernels lie inside ``gated_attn`` and are counted there (and, by
their own names, by `flash_fwd_ms` / `flash_bwd_ms`). With UNSCOPED and
COLLECTIVE the regions sum to the step's busy time, as `_regions.split`
checks. ``tests/benchmark/test_benchmark_qwen3_next.py`` holds every name
the program writes against the lowered text of the program.

GROUPED_PRODUCT are not the program's names but XLA's: on a TPU
`lax.ragged_dot` becomes Mosaic custom calls whose metadata keeps no scope
path, only the operation's own name as its whole path (``ragged-dot-none:``
for a product or one of its transposes, ``ragged-dot-metadata:`` for the
group offsets they read; jax 0.9.0 / libtpu 0.0.34, PERF.md section 7). They
are regions here so that `hybrid_moe_experts_ms` counts the grouped products
it is named for, as `flash_fwd_ms` counts its kernels by their names."""

GROUPED_PRODUCT = ("ragged-dot-none", "ragged-dot-metadata")

HYBRID_TRAIN_STEP = (r"train_step", (
    "gdn_rule", "gdn", "gated_attn", "moe_route", "moe_dispatch",
    "moe_experts", "shared_expert", "embed", "final_norm", "head", "loss",
    "optimizer", *GROUPED_PRODUCT))
