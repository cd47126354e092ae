"""Every region name a reader asks for is a scope in the program it reads:
the lowered text of the trainer's default step, of the paged decode step and
of the three flash kernels carries it as a whole component of a scope path.
A renamed scope fails here instead of silently emptying a metric."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.layer_metrics import _regions
from distributed_pytorch_training_tpu.models import get_model
from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh

TINY = dict(hidden_dim=64, depth=2, num_heads=2, vocab_size=512,
            max_position=64, dtype=jnp.bfloat16)


def scope_paths(lowered) -> set:
    return set(re.findall(r'loc\("(jit\([^"]*)"',
                          lowered.as_text(debug_info=True)))


def regions_found(paths, regions) -> set:
    return {_regions.region_of(p, regions) for p in paths}


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def train_paths(mesh):
    from distributed_pytorch_training_tpu.ops import make_flash_attention_fn
    from distributed_pytorch_training_tpu.parallel.sharding import shard_batch
    from distributed_pytorch_training_tpu.training.loop import (
        TrainConfig, Trainer,
    )
    from distributed_pytorch_training_tpu.training.optim import (
        make_optimizer, make_schedule,
    )
    from distributed_pytorch_training_tpu.training.tasks import (
        LanguageModelingTask,
    )

    model = get_model("gpt2_124m", **TINY, attention_fn=make_flash_attention_fn(
        causal=True, mesh=mesh))
    trainer = Trainer(LanguageModelingTask(compute_dtype=jnp.bfloat16), mesh,
                      TrainConfig(per_device_batch=2, bf16=True),
                      rules=type(model).partition_rules())
    state = trainer.init_state(
        model, np.zeros((1, 64), np.int32),
        make_optimizer("adamw", make_schedule("constant", 3e-4)),
        jax.random.PRNGKey(0))
    batch = shard_batch({"input_ids": np.zeros((2, 64), np.int32),
                         "weight": np.ones(2, np.float32)}, mesh)
    return scope_paths(trainer._train_step.lower(state, batch,
                                                 jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def engine(mesh):
    from distributed_pytorch_training_tpu.serving.continuous import SlotEngine
    from distributed_pytorch_training_tpu.serving.paged import (
        PagedServeConfig,
    )

    model = get_model("gpt2_124m", **TINY)
    params = jax.jit(lambda k: model.init(
        k, np.zeros((1, 16), np.int32), train=False)["params"])(
        jax.random.PRNGKey(0))
    cfg = PagedServeConfig(buckets=(16, 32), rows=4, max_new_tokens=16,
                           serve_dtype="bf16", page_size=8, kv_dtype="fp32",
                           prefix_skip=True)
    return SlotEngine(model, mesh, cfg, params)


@pytest.mark.parametrize("region", _regions.TRAIN_STEP[1])
def test_train_step_carries_region(train_paths, region):
    assert region in regions_found(train_paths, _regions.TRAIN_STEP[1])


@pytest.mark.parametrize("region", _regions.FLASH_KERNELS[1])
def test_train_step_carries_kernel_name(train_paths, region):
    assert region in regions_found(train_paths, _regions.FLASH_KERNELS[1])
    # the kernel's scope lies inside the block's attention scope
    assert any(_regions.region_of(p, (region,)) == region
               and _regions.region_of(p, ("attn",)) == "attn"
               for p in train_paths)


@pytest.mark.parametrize("region", _regions.PAGED_DECODE[1])
def test_paged_decode_carries_region(engine, region):
    paths = scope_paths(engine.lower_paged_decode())
    assert region in regions_found(paths, _regions.PAGED_DECODE[1])


def test_flash_kernels_are_named_in_interpret_mode():
    fa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.flash_attention")
    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    lowered = jax.jit(jax.grad(
        lambda x: fa.flash_attention(x, x, x, True).astype(jnp.float32).sum()
    )).lower(q)
    kernels = _regions.FLASH_KERNELS[1]
    assert regions_found(scope_paths(lowered), kernels) >= set(kernels)
