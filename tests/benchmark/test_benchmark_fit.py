"""Compile-only checks for the described v5e (no chip, nothing runs): the
kernels of the train cells at their real shapes, and the decode step of the
serve cells at the ``rows`` the traffic files fix. They guard the cells'
sizing in every later PR at no chip time.

The topology is described inside a module fixture, never at import: only one
process may load libtpu, and every xdist worker imports every test file.
This is the one file that loads it; keep such tests here.
"""

import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[2]
HBM_BUDGET_GB = 13.0     # the serve cells' rule: pool + temporaries <= 13 GB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip, say why
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def flash(monkeypatch_module):
    fa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.flash_attention")
    monkeypatch_module.setattr(fa, "_interpret", lambda: False)
    return fa


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


def _train_shapes(one_chip):
    cfg = json.loads((ROOT / "benchmark/configs/gpt2_355m.json").read_text())
    mix = json.loads((ROOT / "benchmark/traffic/pretrain_s1024_1chip.json")
                     .read_text())
    size = cfg["published"]
    shape = (mix["per_chip_batch"], mix["seq_len"], size["n_head"],
             size["n_embd"] // size["n_head"])
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)


def test_flash_forward_compiles_at_the_train_cells_shape(flash, one_chip):
    x = _train_shapes(one_chip)
    compiled = jax.jit(lambda q, k, v: flash.flash_attention(
        q, k, v, True, None, 512, 512, None)).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_backward_compiles_at_the_train_cells_shape(flash, one_chip):
    x = _train_shapes(one_chip)

    def loss(q, k, v):
        return flash.flash_attention(q, k, v, True, None, 512, 512,
                                     None).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    # forward with its log-sum-exp, then the dkv and the dq kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_paged_decode_fits_at_the_serve_cells_rows(topo):
    from benchmark.tools import fit_check

    cfg = json.loads((ROOT / "benchmark/configs/gpt2_124m.json").read_text())
    rows = {json.loads((ROOT / f"benchmark/traffic/{m}.json").read_text())
            ["rows"] for m in ("chat_steady", "batch_closed")}
    assert len(rows) == 1, "both serve cells share one `rows`"
    (row,) = fit_check.serve_program_memory(topo, cfg, rows.pop(),
                                            programs=("paged_decode",))
    assert row["peak_gb"] <= HBM_BUDGET_GB, row
    assert row["peak_gb"] >= 8.0, row      # and it fills the chip
