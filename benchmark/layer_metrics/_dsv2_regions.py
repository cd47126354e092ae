"""The named regions of the two programs that serve a latent-attention expert
model (`models/deepseek_v2.py` under `serving/continuous.py`'s `decode` and
`prefill`): tuples beside `_regions.PAGED_DECODE`, read by the same
`_regions.read`. The innermost region counts an operation: the engine's
``model`` scope lies around the whole `apply` and collects only what the
model's own scopes leave (residual adds, the layers' norms); ``mla_attn``
holds the decode kernel (`mla_paged_attention`, also a region of its own, so
that `mla_decode_roofline` reads the kernel by its name and not every custom
call) or, on the gather read, the masked softmax over the views; the prefill's
``mla_attn`` holds the flash forward kernel (``flash_fwd``). The pool's
write is the engine's ``kv_scatter`` whatever a row is made of. With UNSCOPED
and COLLECTIVE the regions sum to the program's busy time, as `_regions.split`
checks. GROUPED_PRODUCT are XLA's names for `lax.ragged_dot`'s custom calls
(`_hybrid_regions.py` has the story). ``tests/benchmark/
test_benchmark_deepseek_v2.py`` holds every name the program writes against
the lowered text of the program."""

from benchmark.layer_metrics._hybrid_regions import GROUPED_PRODUCT
from benchmark.layer_metrics._regions import COLLECTIVE, UNSCOPED

MLA_KERNEL = "mla_paged_attention"
ATTN = (MLA_KERNEL, "mla_attn", "kv_gather", "flash_fwd")
PROJ = ("mla_proj",)
MOE = ("moe_route", "moe_dispatch", "moe_experts", "shared_expert",
       "dense_mlp", *GROUPED_PRODUCT)
OTHER = ("kv_scatter", "embed", "final_norm", "head", "sample",
         "bookkeeping", "model", UNSCOPED, COLLECTIVE)
_NAMED = tuple(r for r in (*ATTN, *PROJ, *MOE, *OTHER)
               if r not in (UNSCOPED, COLLECTIVE))

DSV2_DECODE = (r"jit_decode\b", _NAMED)
DSV2_PREFILL = (r"jit_prefill\b", _NAMED)
