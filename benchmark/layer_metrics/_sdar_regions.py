"""The named regions of the two programs that serve a block-diffusion expert
model (`models/sdar.py` under `serving/block_diffusion.py`'s ``block_step``
and ``prefill``): tuples beside `_regions.PAGED_DECODE`, read by the same
`_regions.read`. The innermost region counts an operation: the engine's
``model`` scope lies around the whole `apply` and collects only what the
model's own scopes leave (residual adds, the layers' norms); ``attn`` holds
the q/k norms, the rotary and the window read, which is the
`paged_attention` kernel (also a region of its own, so that
`sdar_window_attention_roofline` reads the kernel by its name and not every
custom call) or, on the gather read, the views' gather (the engine's
``kv_gather``) and the softmax over them; ``attn_proj`` the four
projections; the prefill's ``attn`` holds the flash forward kernel
(``flash_fwd``). ``unmask`` is the confidences and the choice over the
window's logits. With UNSCOPED and COLLECTIVE the regions sum to the
program's busy time, as `_regions.split` checks. GROUPED_PRODUCT are XLA's
names for `lax.ragged_dot`'s custom calls (`_hybrid_regions.py` has the
story). ``tests/benchmark/test_benchmark_sdar.py`` holds every name the
program writes against the lowered text of the program."""

from benchmark.layer_metrics._hybrid_regions import GROUPED_PRODUCT
from benchmark.layer_metrics._regions import COLLECTIVE, UNSCOPED

WINDOW_KERNEL = "paged_attention"
ATTN = (WINDOW_KERNEL, "attn", "attn_proj", "kv_gather", "flash_fwd")
MOE = ("moe_route", "moe_dispatch", "moe_experts", *GROUPED_PRODUCT)
HEAD_UNMASK = ("final_norm", "head", "unmask")
OTHER = ("kv_scatter", "embed", "bookkeeping", "model", UNSCOPED, COLLECTIVE)
_NAMED = tuple(r for r in (*ATTN, *MOE, *HEAD_UNMASK, *OTHER)
               if r not in (UNSCOPED, COLLECTIVE))

SDAR_BLOCK_STEP = (r"jit_block_step\b", _NAMED)
SDAR_PREFILL = (r"jit_prefill\b", _NAMED)


def counted(run, name: str) -> float:
    """The sum of one of the scheduler's counters over the traced run."""
    return sum(float(e["value"]) for e in run.events
               if e.get("kind") == "counter" and e.get("name") == name)
