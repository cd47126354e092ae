"""serving/ — manifest-verified batched inference on the training stack.

The path from a training checkpoint to a served token, built from the
pieces the training side already ships: ``training/checkpoint.py``'s
manifest-verified restore for the weights, ``data/pack.py``'s bucket
ladder for the shapes, ``models/gpt2.py``'s cache-aware forward for
prefill + KV-cache decode, the grad-sync int8 codec grid for
weight-at-rest quantization, ``resilience/`` for liveness + drain, and
``telemetry/`` for the latency story (queue_wait / prefill / decode /
drain spans).

Two batching disciplines share the stack. The iteration-granular path
(`InferenceEngine` + `serve_forever`) forms a batch, decodes it to
completion, forms the next. The token-granular path (`SlotEngine` +
`ContinuousScheduler`, ISSUE 17) keeps ONE compiled decode program
running over a fixed slot pool backed by a paged — optionally int8 —
KV cache (`PagedServeConfig` / `PagePool`), admitting and retiring
requests between tokens with zero recompiles. `Router` spreads requests
over N replicas of either and resubmits on replica death with the
request's sampling seed pinned, so a retried request samples the
identical stream.

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
