"""Runtime layer: per-rank seed rule + DistContext basics.

The reference de-correlates host RNG across ranks with `seed + rank`
(/root/reference/train_ddp.py:76-78); the TPU design keeps device-side keys
shared (SPMD traces must agree) but host-side streams must follow the rule.
"""

import numpy as np

from distributed_pytorch_training_tpu.runtime import (
    per_process_seed, set_seed, setup_distributed,
)


def test_per_process_seed_matches_reference_rule():
    # the exact seed+rank arithmetic of ref :76-78
    for rank in range(4):
        assert per_process_seed(42, rank) == 42 + rank


def test_set_seed_decorrelates_processes():
    rng0 = set_seed(42, process_index=0)
    draw0 = rng0.integers(0, 2**31, 16)
    np0 = np.random.randint(0, 2**31, 16)  # global numpy stream, rank 0

    rng1 = set_seed(42, process_index=1)
    draw1 = rng1.integers(0, 2**31, 16)
    np1 = np.random.randint(0, 2**31, 16)  # global numpy stream, rank 1

    assert not np.array_equal(draw0, draw1), "per-rank streams must differ"
    assert not np.array_equal(np0, np1), "global numpy stream must differ too"

    # and the rule is reproducible: same (seed, rank) -> same stream
    again = set_seed(42, process_index=1).integers(0, 2**31, 16)
    np.testing.assert_array_equal(draw1, again)


def test_set_seed_rank_uses_runtime_process_index():
    # single-process runtime: default rank is 0 -> identical to explicit 0
    a = set_seed(7).integers(0, 2**31, 8)
    b = set_seed(7, process_index=0).integers(0, 2**31, 8)
    np.testing.assert_array_equal(a, b)


def test_setup_distributed_single_process_context():
    ctx = setup_distributed()
    assert ctx.process_index == 0
    assert ctx.process_count == 1
    assert ctx.is_main


def test_platform_rule_cpu_only_by_name(monkeypatch):
    """runtime.require_backend: JAX_PLATFORMS=cpu is a CPU run; anything
    else (unset included) with no TPU behind it raises instead of carrying
    on quietly on the host."""
    import pytest

    from distributed_pytorch_training_tpu.runtime import (
        cpu_requested, require_backend,
    )

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert cpu_requested() and require_backend() == "cpu"
    for value in (None, "", "tpu,cpu"):
        if value is None:
            monkeypatch.delenv("JAX_PLATFORMS")
        else:
            monkeypatch.setenv("JAX_PLATFORMS", value)
        assert not cpu_requested()
        with pytest.raises(RuntimeError, match="did not ask for the CPU"):
            require_backend()


def test_serving_cli_never_sets_the_platform(monkeypatch):
    """The serving CLI used to force JAX_PLATFORMS=cpu whenever the variable
    was unset — a TPU would have sat idle beside an 8-device CPU run. Now
    the virtual mesh is asked for only on a CPU run named as such, the
    platform is never touched, and an unnamed CPU run is refused."""
    import os

    import jax
    import pytest

    from distributed_pytorch_training_tpu.analysis.__main__ import (
        _ensure_test_mesh,
    )
    from distributed_pytorch_training_tpu.serving.__main__ import main

    platforms_before = jax.config.jax_platforms
    monkeypatch.delenv("JAX_PLATFORMS")
    _ensure_test_mesh()
    assert "JAX_PLATFORMS" not in os.environ
    assert jax.config.jax_platforms == platforms_before
    with pytest.raises(RuntimeError, match="did not ask for the CPU"):
        main(["smoke", "--no-telemetry"])


def test_compile_cache_refuses_cpu_backend_in_auto(monkeypatch):
    """XLA:CPU persistent-cache reloads are unsafe (AOT pseudo-feature
    mismatch desynchronized a collective rendezvous into a fatal abort —
    runtime.dist.enable_persistent_compile_cache docstring). On the CPU
    test backend the default "auto" mode must refuse and leave the config
    untouched."""
    import jax

    from distributed_pytorch_training_tpu.runtime import (
        CACHE_DIR_ENV, enable_persistent_compile_cache,
    )

    monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert enable_persistent_compile_cache() is False
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_tristate(monkeypatch):
    """ISSUE-11: the DPT_COMPILE_CACHE tri-state — "off" never enables,
    "on" forces (the operator vouches), invalid values are loud, unset
    resolves to "auto" (the backend-gated behavior)."""
    import jax
    import pytest

    from distributed_pytorch_training_tpu.runtime import (
        CACHE_DIR_ENV, COMPILE_CACHE_ENV, compile_cache_mode,
        enable_persistent_compile_cache,
    )

    monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    dir_before = jax.config.jax_compilation_cache_dir

    monkeypatch.setenv(COMPILE_CACHE_ENV, "off")
    assert compile_cache_mode() == "off"
    assert enable_persistent_compile_cache() is False
    assert jax.config.jax_compilation_cache_dir == dir_before

    monkeypatch.setenv(COMPILE_CACHE_ENV, "maybe")
    with pytest.raises(ValueError, match="DPT_COMPILE_CACHE"):
        compile_cache_mode()

    monkeypatch.delenv(COMPILE_CACHE_ENV, raising=False)
    assert compile_cache_mode() == "auto"
    assert compile_cache_mode("on") == "on"  # explicit arg beats the env


def test_compile_cache_lives_in_one_place(tmp_path, monkeypatch):
    """ONE rule (runtime/dist.py): with JAX_COMPILATION_CACHE_DIR set, jax
    reads it itself and our code never touches jax_compilation_cache_dir
    (and creates nothing under the checkout); unset, the directory is the
    fixed <checkout>/.jax_cache. No caller can pass a directory."""
    import inspect
    from pathlib import Path

    import jax

    from distributed_pytorch_training_tpu.runtime import (
        CACHE_DIR_ENV, compile_cache_dir, enable_persistent_compile_cache,
    )

    checkout = Path(__file__).resolve().parent.parent
    assert list(inspect.signature(
        enable_persistent_compile_cache).parameters) == ["mode"]
    assert not inspect.signature(compile_cache_dir).parameters

    dir_before = jax.config.jax_compilation_cache_dir
    enabled_before = jax.config.jax_enable_compilation_cache
    metadata_before = \
        jax.config.jax_compilation_cache_include_metadata_in_key
    frames_before = jax.config.jax_traceback_in_locations_limit
    had_checkout_cache = (checkout / ".jax_cache").exists()
    try:
        # variable set: the config is jax's to read, not ours to write
        monkeypatch.setenv(CACHE_DIR_ENV, str(tmp_path / "cache"))
        assert compile_cache_dir() == tmp_path / "cache"
        assert enable_persistent_compile_cache(mode="on") is True
        assert jax.config.jax_compilation_cache_dir == dir_before
        # scope names are what a trace is read by: they are in the key,
        # and neither this file's name nor a line number is
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        text = jax.jit(jax.named_scope("a_region")(lambda x: x + 1)).lower(
            1.0).as_text(debug_info=True)
        assert "a_region/add" in text and "test_runtime" not in text
        assert (checkout / ".jax_cache").exists() == had_checkout_cache
        # ...and a refusal (auto on XLA:CPU) switches jax's own cache off
        assert enable_persistent_compile_cache() is False
        assert jax.config.jax_enable_compilation_cache is False

        # variable unset: the fixed checkout-local directory
        monkeypatch.delenv(CACHE_DIR_ENV)
        assert compile_cache_dir() == checkout / ".jax_cache"
        assert enable_persistent_compile_cache(mode="on") is True
        assert jax.config.jax_compilation_cache_dir == \
            str(checkout / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", dir_before)
        jax.config.update("jax_enable_compilation_cache", enabled_before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          metadata_before)
        jax.config.update("jax_traceback_in_locations_limit", frames_before)

    # and no other module decides: the config key appears in dist.py only
    from distributed_pytorch_training_tpu.analysis.ast_rules import (
        iter_source_files,
    )

    setters = [p.relative_to(checkout).as_posix()
               for p in iter_source_files()
               if "jax_compilation_cache_dir" in p.read_text()]
    assert setters == ["distributed_pytorch_training_tpu/runtime/dist.py"]
