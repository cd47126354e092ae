"""serving/ — manifest-verified checkpoints served on the training stack.

The path from a training checkpoint to a served token, built from the
pieces the training side already ships: ``training/checkpoint.py``'s
manifest-verified restore for the weights, ``data/pack.py``'s bucket
ladder for the shapes, the models' cache-aware forwards for prefill and
decode over a paged KV pool, the grad-sync int8 codec grid for
weight-at-rest quantization, ``resilience/`` for liveness + drain, and
``telemetry/`` for the latency story (queue_wait / slot_wait / prefill /
drain spans and the scheduler's phases).

A causal LM has ONE server, the token server: `SlotEngine` (or the engine
its model asks for: `BlockDiffusionEngine`, `SpeculativeEngine`) keeps
one compiled decode program running over a fixed slot pool backed by a
paged — optionally int8 — KV cache (`PagedServeConfig` / `PagePool`), and
its `ContinuousScheduler` admits and retires requests between tokens with
zero recompiles. `Router` spreads requests over N replicas and resubmits
on replica death with the request's sampling seed pinned, so a retried
request samples the identical stream. Models without a cache have the
forward engine: `InferenceEngine` (BERT's bucketed forward behind
`serve_forever`, `serve_images` for ResNet / ViT). Both stand on
`engine.ServedModel` (placement, int8 weights, checkpoint provenance,
the compile census).

Entry points: the ``serving`` console script (``smoke`` / ``bench`` /
``serve`` / ``fleet``), or the classes directly.
"""

from .batching import Request, RequestQueue, Result, drain, serve_forever
from .continuous import (
    ContinuousScheduler, SlotEngine, sample_tokens, serve_continuous,
)
from .engine import (
    InferenceEngine, QuantizedLeaf, ServeConfig, dequantize_params,
    int8_weight_bytes, quantize_params,
)
from ..models.layers import paged_kv_bytes
from .paged import PagedServeConfig, PagePool
from .router import (
    HttpReplica, InProcessReplica, ReplicaDead, Router, RouterRequest,
)

__all__ = [
    "ContinuousScheduler", "HttpReplica", "InProcessReplica",
    "InferenceEngine", "PagePool", "PagedServeConfig", "QuantizedLeaf",
    "ReplicaDead", "Request", "RequestQueue", "Result", "Router",
    "RouterRequest", "ServeConfig", "SlotEngine",
    "dequantize_params", "drain", "int8_weight_bytes", "paged_kv_bytes",
    "quantize_params", "sample_tokens", "serve_continuous",
    "serve_forever",
]
