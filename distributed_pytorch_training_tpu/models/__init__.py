"""Model zoo — TPU-native replacements for the torchvision/transformers models
the reference leans on (/root/reference/train_ddp.py:154 and BASELINE.json:6-12):
ResNet-18/50, ViT-B/16, BERT-base (MLM), GPT-2 355M.

All models are flax.linen modules with:
* `dtype` (compute) vs `param_dtype` (storage) split — the bf16 mixed-precision
  path (the reference's `--amp`, train_ddp.py:203-209, without a GradScaler:
  bf16 keeps fp32's exponent range);
* a `partition_rules()` classmethod giving TP/FSDP PartitionSpecs for the
  mesh axes defined in `parallel.mesh`.
"""

from .registry import get_model, list_models, register_model  # noqa: F401
from . import resnet  # noqa: F401  (registers resnet18/resnet50)
from . import vit  # noqa: F401  (registers vit_b16)
from . import bert  # noqa: F401  (registers bert_base)
from . import gpt2  # noqa: F401  (registers gpt2_355m/gpt2_124m)
from . import moe  # noqa: F401  (registers gpt2_moe)
from . import qwen3_next  # noqa: F401  (registers qwen3_next_80b_a3b)
from . import deepseek_v2  # noqa: F401  (registers deepseek_v2_236b_a21b)
from . import sdar  # noqa: F401  (registers sdar_30b_a3b_chat)
