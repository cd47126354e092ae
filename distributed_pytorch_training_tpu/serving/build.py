"""How a serving engine is built from a model's name: the checkpoint
template, the position table sized from buckets or pages, mesh validation
and the serve rules — one code path for every engine (the forward engine
of BERT and image models, the token server's three of a causal LM),
behind every subcommand of the CLI (`serving/__main__.py`) and its load
test (`serving/loadtest.py`)."""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.registry import is_lm_model


def has_cache(model_name: str, model_overrides: Optional[dict] = None) -> bool:
    """Whether the model is a causal LM (it keeps a KV cache: `init_cache`).
    Those are the token server's (`build_slot_engine`); every other model
    is the forward engine's. Asked by `smoke` and `bench` before any engine
    is built; the two constructors refuse the wrong kind on their own."""
    from ..models import get_model

    return hasattr(get_model(model_name, **(model_overrides or {})),
                   "init_cache")


def build_serving_engine(devices: Sequence[jax.Device], model_name: str,
                         buckets: Sequence[int] = (16, 32), rows: int = 8,
                         max_new_tokens: int = 8, serve_dtype: str = "fp32",
                         model_overrides: Optional[dict] = None,
                         ckpt_dir: Optional[str] = None,
                         train_config=None, seed: int = 0,
                         optimizer: str = "auto", momentum: float = 0.9,
                         weight_decay: float = 5e-4,
                         mesh_spec: Optional[str] = None,
                         config=None, engine_cls=None,
                         min_positions: int = 0):
    """(engine, mesh) for a serving config: the one place a checkpoint
    template, a mesh and an engine class meet, behind the CLI's `serve`,
    `smoke` and `bench`. Called bare it builds the forward engine
    (`InferenceEngine`: BERT, image models; it refuses a causal LM, whose
    builder is `build_slot_engine`). Without
    ``ckpt_dir`` the weights are random-init (a smoke of the serving
    path, not a served model — the row says so); with it, the
    newest manifest-verified checkpoint restores through the same template
    machinery a training resume uses (``train_config`` carries the
    training run's zero1/fsdp/wire flags when they differ from defaults).

    ``config``/``engine_cls`` swap in a richer config + engine pair
    (`build_slot_engine` passes PagedServeConfig + SlotEngine) while every
    other knob — checkpoint templates, mesh validation, vocab/positions
    sizing — stays this one code path; ``min_positions`` widens the LM's
    position table when the engine's padded view (pages) outgrows
    ``max(buckets) + max_new_tokens``.

    The restore template's optimizer chain must STRUCTURALLY match the
    training run's (orbax validates the opt_state tree): the template is
    built exactly as train.py builds it — ``make_optimizer`` with a
    callable (constant) schedule and no grad clip — and ``optimizer`` /
    ``momentum`` / ``weight_decay`` are the knobs that change the chain's
    structure (a zero momentum/decay drops a transform). "auto" picks the
    family recipe: adamw for LM models, sgd for vision (train.py's CLI
    default is sgd everywhere; pass ``optimizer="sgd"`` for an LM trained
    that way).
    """
    from ..models import get_model
    from ..parallel import MeshSpec, build_mesh
    from .engine import InferenceEngine, ServeConfig
    from ..training.optim import make_optimizer, make_schedule

    # --mesh (ISSUE 13 satellite): default stays the 1-D pure-DP mesh —
    # every existing invocation unchanged; "data=N,model=M" serves big
    # models TP-sharded over the model axis via the GSPMD rules
    # (validate_mesh rejects axes the served model cannot use).
    spec = (MeshSpec.parse(mesh_spec) if mesh_spec
            else MeshSpec(data=len(devices)))
    mesh = build_mesh(spec, devices=list(devices))
    cfg = config if config is not None else ServeConfig(
        buckets=tuple(buckets), rows=rows,
        max_new_tokens=max_new_tokens, serve_dtype=serve_dtype)
    serve_dtype = cfg.serve_dtype
    dtype = jnp.bfloat16 if serve_dtype == "bf16" else jnp.float32
    if optimizer == "auto":
        optimizer = "adamw" if is_lm_model(model_name) else "sgd"
    tx = make_optimizer(optimizer, make_schedule("constant", 0.1),
                        momentum=momentum, weight_decay=weight_decay)
    if not is_lm_model(model_name):
        # --model-overrides applies here too: a resnet trained with
        # num_classes=100 must be able to build a matching template
        model = get_model(model_name, dtype=dtype,
                          **(model_overrides or {}))
        sample = np.zeros((1, 32, 32, 3), np.float32)
    else:
        kwargs = dict(model_overrides or {})
        need = max(max(cfg.buckets) + cfg.max_new_tokens, min_positions)
        kwargs.setdefault("max_position", max(512, need))
        model = get_model(model_name, dtype=dtype, **kwargs)
        sample = np.zeros((1, min(cfg.buckets)), np.int32)
    rules = (type(model).partition_rules()
             if hasattr(type(model), "partition_rules") else None)
    from ..parallel.mesh import validate_mesh

    validate_mesh(mesh, rules=rules)
    serve_rules = rules if dict(mesh.shape).get("model", 1) > 1 else None
    cls = engine_cls if engine_cls is not None else InferenceEngine
    if ckpt_dir:
        engine = cls.from_checkpoint(
            ckpt_dir, model, mesh, cfg, tx, sample,
            train_config=train_config, rules=serve_rules)
    else:
        # one jitted call that returns the weights alone: the forward pass
        # `init` traces is then dead code, and every leaf is made on the
        # devices in its own dtype (a model of billions of parameters has
        # no float32 copy and no eager forward to wait for)
        from ..parallel.sharding import replicated

        variables = jax.jit(
            lambda key: {k: v for k, v in model.init(
                key, sample, train=False).items()
                if k in ("params", "batch_stats")},
            out_shardings=replicated(mesh))(jax.random.PRNGKey(seed))
        engine = cls(model, mesh, cfg, variables["params"],
                     batch_stats=variables.get("batch_stats"),
                     rules=serve_rules)
    return engine, mesh


def build_slot_engine(devices: Sequence[jax.Device], model_name: str,
                      buckets: Sequence[int] = (8, 16), rows: int = 8,
                      max_new_tokens: int = 8, kv_dtype: str = "fp32",
                      page_size: int = 8, prefix_sharing: bool = True,
                      n_pages: int = 0, prefix_skip: bool = True,
                      serve_dtype: str = "fp32", **kw):
    """(SlotEngine, mesh): a causal LM's engine, through
    `build_serving_engine` (same checkpoint templates, mesh validation and
    sizing; ``**kw`` forwards model_overrides/ckpt_dir/train_config/...).
    The engine decodes over a paged, optionally int8 KV pool
    (serving/continuous.py); ``min_positions`` is derived here because the
    gathered dense view is ``pages_per_slot * page_size`` wide — page
    padding can outgrow ``max(buckets) + max_new_tokens``."""
    from ..models import get_model
    from .block_diffusion import BlockDiffusionEngine
    from .continuous import SlotEngine
    from .paged import PagedServeConfig

    cfg = PagedServeConfig(
        buckets=tuple(buckets), rows=rows, max_new_tokens=max_new_tokens,
        serve_dtype=serve_dtype, page_size=page_size, kv_dtype=kv_dtype,
        n_pages=n_pages, prefix_sharing=prefix_sharing,
        prefix_skip=prefix_skip)
    # the engine a model asks for, by what the model says of itself: one
    # that generates by blocks is served a block a step
    probe = get_model(model_name, **(kw.get("model_overrides") or {}))
    by_blocks = getattr(probe, "block_length", 1) > 1
    return build_serving_engine(
        devices, model_name, buckets=buckets, rows=rows,
        max_new_tokens=max_new_tokens, config=cfg,
        engine_cls=BlockDiffusionEngine if by_blocks else SlotEngine,
        min_positions=cfg.pages_per_slot * cfg.page_size, **kw)


def build_spec_engine(devices: Sequence[jax.Device], model_name: str,
                      draft_model_name: str,
                      buckets: Sequence[int] = (8, 16), rows: int = 8,
                      max_new_tokens: int = 8, page_size: int = 8,
                      prefix_sharing: bool = True, n_pages: int = 0,
                      prefix_skip: bool = True, draft_k: int = 4,
                      draft_overrides: Optional[dict] = None,
                      seed: int = 0, **kw):
    """(SpeculativeEngine, mesh) — `build_slot_engine` with a draft LM
    riding along. The target side goes through the exact
    `build_serving_engine` path (checkpoint templates, mesh validation,
    position sizing) via an engine_cls closure that injects the draft;
    the draft itself is ALWAYS random-init fp32 here (it is a throughput
    device, not a served artifact — acceptance is exact-match against the
    target, so draft weights change speed, never the emitted stream).

    The draft model's position table is sized from the DRAFT padded view:
    speculative.py widens ``max_new_tokens`` by K (the last propose run of
    a request writes draft k/v past the target frontier), so its
    pages_per_slot can outgrow the target's.
    """
    from ..models import get_model
    from .paged import PagedServeConfig
    from .speculative import SpeculativeEngine

    cfg = PagedServeConfig(
        buckets=tuple(buckets), rows=rows, max_new_tokens=max_new_tokens,
        page_size=page_size, kv_dtype="fp32", n_pages=n_pages,
        prefix_sharing=prefix_sharing, prefix_skip=prefix_skip)
    dcfg = dataclasses.replace(
        cfg, max_new_tokens=max_new_tokens + draft_k, n_pages=0)
    dkwargs = dict(draft_overrides or {})
    dkwargs.setdefault("max_position",
                       max(512, dcfg.pages_per_slot * dcfg.page_size))
    draft = get_model(draft_model_name, dtype=jnp.float32, **dkwargs)
    dvars = draft.init(jax.random.PRNGKey(seed + 1),
                       np.zeros((1, min(cfg.buckets)), np.int32),
                       train=False)

    class _SpecEngine(SpeculativeEngine):
        def __init__(self, model, mesh, config, params, **ekw):
            super().__init__(model, mesh, config, params, draft,
                             dvars["params"], spec_k=draft_k, **ekw)

    return build_serving_engine(
        devices, model_name, buckets=buckets, rows=rows,
        max_new_tokens=max_new_tokens, config=cfg, engine_cls=_SpecEngine,
        min_positions=cfg.pages_per_slot * cfg.page_size, seed=seed, **kw)
