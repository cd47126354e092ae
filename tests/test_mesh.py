"""Mesh construction tests (SURVEY.md §7 step 1)."""

import jax
import numpy as np
import pytest

from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
from distributed_pytorch_training_tpu.parallel.mesh import (
    DATA,
    MODEL,
    SEQ,
    batch_shard_count,
    local_batch_size,
)


def test_default_spec_is_pure_dp(devices):
    mesh = build_mesh(devices=devices)
    assert mesh.shape[DATA] == 8
    assert all(v == 1 for k, v in mesh.shape.items() if k != DATA)


def test_wildcard_fills_remaining(devices):
    mesh = build_mesh(MeshSpec(data=-1, model=2), devices=devices)
    assert mesh.shape[DATA] == 4
    assert mesh.shape[MODEL] == 2


def test_3d_mesh(devices):
    mesh = build_mesh(MeshSpec(data=2, model=2, seq=2), devices=devices)
    assert mesh.shape[DATA] == 2
    assert mesh.shape[MODEL] == 2
    assert mesh.shape[SEQ] == 2
    assert mesh.size == 8


def test_bad_shapes_raise(devices):
    with pytest.raises(ValueError):
        build_mesh(MeshSpec(data=3), devices=devices)  # 3 does not divide 8
    with pytest.raises(ValueError):
        MeshSpec(data=-1, model=-1).resolved(8)  # two wildcards


def test_mesh_spec_parse():
    spec = MeshSpec.parse("data=4,model=2")
    assert spec.data == 4 and spec.model == 2 and spec.seq == 1


def test_batch_shard_count_and_local_batch(devices):
    mesh = build_mesh(MeshSpec(data=4, model=2), devices=devices)
    assert batch_shard_count(mesh) == 4
    # per-device batch 16 (ref train_ddp.py:27 semantic), single host:
    # local batch == global batch == 16 * 4 data-shards.
    assert local_batch_size(16, mesh) == 64


def test_all_devices_used_once(devices):
    mesh = build_mesh(MeshSpec(data=2, seq=4), devices=devices)
    ids = sorted(d.id for d in np.asarray(mesh.devices).flat)
    assert ids == sorted(d.id for d in devices)


def test_layout_refusal_is_an_error_on_tpu_and_a_reshape_on_cpu(devices,
                                                                monkeypatch):
    """When the topology helper refuses a layout, a plain reshape is right
    for CPU test meshes (no topology to respect) and WRONG on a TPU, where
    it would put collectives on the wrong links without saying so."""
    from jax.experimental import mesh_utils

    from distributed_pytorch_training_tpu.parallel.mesh import (
        _VirtualSliceDevice,
    )

    def refuse(*a, **kw):
        raise ValueError("no such layout")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    assert build_mesh(MeshSpec(data=8), devices=devices).size == 8

    class TpuDressed(_VirtualSliceDevice):
        platform = "tpu"

    tpus = [TpuDressed(d, 0) for d in devices]
    with pytest.raises(ValueError, match="no such layout"):
        build_mesh(MeshSpec(data=8), devices=tpus)


def test_mesh_spec_parse_errors():
    with pytest.raises(ValueError, match="unknown axis"):
        MeshSpec.parse("bogus=2")
    with pytest.raises(ValueError, match="expected"):
        MeshSpec.parse("data")
    with pytest.raises(ValueError, match="expected"):
        MeshSpec.parse("data=x")


def test_mesh_spec_rejects_zero_and_negative():
    with pytest.raises(ValueError, match="axis size"):
        MeshSpec.parse("data=0")
    with pytest.raises(ValueError, match="axis size"):
        MeshSpec.parse("data=-3")
    with pytest.raises(ValueError, match=">= 1"):
        MeshSpec(data=0).resolved(8)


class TestValidateMeshUsage:
    """--mesh axes the config cannot use must fail loudly, not waste devices
    (VERDICT r2 #6: `--mesh pipe=2` silently replicated all work)."""

    def _mesh(self, devices, **kw):
        from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
        return build_mesh(MeshSpec(**kw), devices=devices)

    def test_pipe_without_pipeline_rejected(self, devices):
        import pytest
        from distributed_pytorch_training_tpu.parallel.mesh import validate_mesh_usage
        mesh = self._mesh(devices, pipe=2, data=4)
        with pytest.raises(ValueError, match="pipe=2"):
            validate_mesh_usage(mesh, pipelined=False)
        validate_mesh_usage(mesh, pipelined=True)  # and the cure works

    def test_seq_without_seq_attention_rejected(self, devices):
        import pytest
        from distributed_pytorch_training_tpu.parallel.mesh import validate_mesh_usage
        mesh = self._mesh(devices, seq=2, data=4)
        with pytest.raises(ValueError, match="seq=2"):
            validate_mesh_usage(mesh, attention="xla")
        validate_mesh_usage(mesh, attention="ring")
        validate_mesh_usage(mesh, attention="ulysses")

    def test_expert_without_moe_rejected(self, devices):
        import pytest
        from distributed_pytorch_training_tpu.parallel.mesh import validate_mesh_usage
        mesh = self._mesh(devices, expert=2, data=4)
        with pytest.raises(ValueError, match="expert=2"):
            validate_mesh_usage(mesh, is_moe=False)
        validate_mesh_usage(mesh, is_moe=True)

    def test_model_axis_needs_tp_rules(self, devices):
        import pytest
        from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
        from distributed_pytorch_training_tpu.models.resnet import ResNet
        from distributed_pytorch_training_tpu.parallel.mesh import validate_mesh_usage
        mesh = self._mesh(devices, model=2, data=4)
        with pytest.raises(ValueError, match="model=2"):
            validate_mesh_usage(mesh, rules=ResNet.partition_rules())
        validate_mesh_usage(mesh, rules=GPT2LMHead.partition_rules())

    def test_fsdp_without_fsdp_rules_warns_not_raises(self, devices, caplog):
        import logging
        from distributed_pytorch_training_tpu.models.resnet import ResNet
        from distributed_pytorch_training_tpu.parallel.mesh import validate_mesh_usage
        mesh = self._mesh(devices, fsdp=2, data=4)
        with caplog.at_level(logging.WARNING):
            validate_mesh_usage(mesh, rules=ResNet.partition_rules())
        assert any("fsdp=2" in r.getMessage() for r in caplog.records)

    def test_pure_dp_mesh_always_valid(self, mesh8):
        from distributed_pytorch_training_tpu.parallel.mesh import validate_mesh_usage
        validate_mesh_usage(mesh8)


class TestHybridDcnMesh:
    """Multi-slice (DCN-joined) pods get a hybrid mesh: slice-spanning
    parallelism on the latency-tolerant axes only (VERDICT r3 #7)."""

    def test_dcn_factors_data_first(self):
        from distributed_pytorch_training_tpu.parallel.mesh import (
            AXIS_ORDER, dcn_factors,
        )

        sizes = dict(pipe=1, data=8, fsdp=1, expert=1, seq=1, model=4)
        per, dcn = dcn_factors(sizes, n_slices=4)
        assert dcn["data"] == 4 and per["data"] == 2
        assert per["model"] == 4 and dcn["model"] == 1  # TP stays on ICI
        import math
        assert math.prod(dcn[a] for a in AXIS_ORDER) == 4
        for a in AXIS_ORDER:
            # absent axes (the newer explicit `slice`) count as size 1
            assert per[a] * dcn[a] == sizes.get(a, 1)

    def test_dcn_factors_spills_to_pipe_and_fsdp(self):
        from distributed_pytorch_training_tpu.parallel.mesh import dcn_factors

        sizes = dict(pipe=2, data=2, fsdp=2, expert=1, seq=1, model=1)
        per, dcn = dcn_factors(sizes, n_slices=8)
        assert (dcn["data"], dcn["pipe"], dcn["fsdp"]) == (2, 2, 2)
        assert (per["data"], per["pipe"], per["fsdp"]) == (1, 1, 1)

    def test_dcn_factors_rejects_model_axis_spill(self):
        from distributed_pytorch_training_tpu.parallel.mesh import dcn_factors

        # only model-parallelism available to span slices -> must refuse
        sizes = dict(pipe=1, data=1, fsdp=1, expert=1, seq=1, model=8)
        with pytest.raises(ValueError, match="ICI"):
            dcn_factors(sizes, n_slices=2)

    def test_build_mesh_uses_hybrid_layout_on_multislice(self, devices,
                                                         monkeypatch):
        """Mocked 2-slice device set: build_mesh must call
        create_hybrid_device_mesh with the dcn split on the data axis."""
        from jax.experimental import mesh_utils

        from distributed_pytorch_training_tpu.parallel.mesh import (
            AXIS_ORDER, MeshSpec, build_mesh,
        )

        class FakeDev:
            platform = "cpu"

            def __init__(self, i, slice_index):
                self.id = i
                self.slice_index = slice_index

        fakes = [FakeDev(i, slice_index=i // 4) for i in range(8)]
        calls = {}

        def fake_hybrid(mesh_shape, dcn_mesh_shape, devices=None):
            calls["mesh_shape"] = tuple(mesh_shape)
            calls["dcn_mesh_shape"] = tuple(dcn_mesh_shape)
            import numpy as np
            return np.asarray(jax.devices()).reshape(
                tuple(m * d for m, d in zip(mesh_shape, dcn_mesh_shape)))

        monkeypatch.setattr(mesh_utils, "create_hybrid_device_mesh",
                            fake_hybrid)
        mesh = build_mesh(MeshSpec(data=4, model=2), devices=fakes)
        # per-slice: data=2, model=2; across DCN: data=2
        i_data = AXIS_ORDER.index("data")
        i_model = AXIS_ORDER.index("model")
        assert calls["dcn_mesh_shape"][i_data] == 2
        assert calls["mesh_shape"][i_data] == 2
        assert calls["dcn_mesh_shape"][i_model] == 1
        assert calls["mesh_shape"][i_model] == 2
        assert dict(mesh.shape)["data"] == 4 and dict(mesh.shape)["model"] == 2

    def test_single_slice_devices_skip_hybrid(self, devices):
        """CPU test devices carry no slice_index: the plain path runs."""
        from distributed_pytorch_training_tpu.parallel.mesh import (
            MeshSpec, build_mesh,
        )

        mesh = build_mesh(MeshSpec(data=8), devices=devices)
        assert dict(mesh.shape)["data"] == 8
