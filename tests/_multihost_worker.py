"""Worker process for the 2-process multi-host test (test_multihost.py).

Runs as one of DPT_NUM_PROCESSES=2 processes on the CPU backend, each with 2
virtual local devices — the smallest honest model of a 2-host TPU pod slice
(the env:// rendezvous contract of /root/reference/train_ddp.py:53-68).
Every assertion here runs in BOTH processes; any failure exits non-zero and
the parent test fails.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_num_cpu_devices", 2)  # parent sets JAX_PLATFORMS=cpu

import flax.linen as nn
import jax.numpy as jnp
import numpy as np


def main() -> None:
    from distributed_pytorch_training_tpu.parallel import (
        MeshSpec, barrier, broadcast_from_main, build_mesh, host_all_gather,
        shard_batch,
    )
    from distributed_pytorch_training_tpu.parallel.collectives import (
        reduce_scalar,
    )
    from distributed_pytorch_training_tpu.runtime import (
        cleanup_distributed, per_process_seed, setup_distributed,
    )

    ctx = setup_distributed()
    rank = ctx.process_index

    # runtime topology: 2 processes x 2 local devices = 4 global
    assert ctx.process_count == 2, ctx
    assert ctx.local_device_count == 2, ctx
    assert ctx.device_count == 4, ctx
    assert ctx.is_main == (rank == 0)
    assert per_process_seed(42) == 42 + rank  # ref :76-78 rule, live runtime

    # host-level collectives (the dist.barrier / rank-0 broadcast surface)
    barrier("start")
    got = broadcast_from_main(np.float32(123.0 + 7 * rank))
    assert float(got) == 123.0, got  # everyone sees process 0's value

    total = reduce_scalar(rank + 1, op="sum")  # 1 + 2
    assert total == 3.0, total
    gathered = np.asarray(host_all_gather(np.float32(rank)))
    np.testing.assert_array_equal(np.sort(gathered.ravel()), [0.0, 1.0])

    # 2-process shard_batch -> sharded TRAIN step over the global mesh
    mesh = build_mesh(MeshSpec(data=4))
    global_batch, local_batch = 8, 4

    class TinyNet(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape(x.shape[0], -1)
            x = nn.gelu(nn.Dense(16)(x))
            return nn.Dense(10)(x)

    from distributed_pytorch_training_tpu.training import TrainConfig, Trainer
    from distributed_pytorch_training_tpu.training.optim import sgd
    from distributed_pytorch_training_tpu.training.tasks import (
        ImageClassificationTask,
    )

    task = ImageClassificationTask(mean=(0.5, 0.5, 0.5), std=(0.25, 0.25, 0.25),
                                   augment=False)
    trainer = Trainer(task, mesh, TrainConfig(seed=0))
    state = trainer.init_state(TinyNet(), np.zeros((1, 8, 8, 3), np.float32),
                               sgd(0.1), jax.random.PRNGKey(0))

    # every process contributes ITS OWN slice of the global batch (the
    # multi-host generalization of DistributedSampler, ref :122-127) — and
    # the data is rank-dependent, so a correct global reduction must see both
    rng = np.random.RandomState(100 + rank)
    local = {
        "image": rng.randint(0, 256, (local_batch, 8, 8, 3)).astype(np.uint8),
        "label": rng.randint(0, 10, local_batch).astype(np.int32),
        "weight": np.ones(local_batch, np.float32),
    }
    batch = shard_batch(local, mesh)
    assert batch["image"].shape[0] == global_batch  # global view
    # this process holds only its local shard's rows
    own = sum(int(np.prod(s.data.shape[:1]))
              for s in batch["image"].addressable_shards)
    assert own == local_batch, own

    losses = []
    key = jax.random.PRNGKey(1)
    for _ in range(4):
        state, metrics = trainer._train_step(state, batch, key)
        # metrics are replicated => identical on both processes
        w = float(jax.device_get(metrics["weight"]))
        assert w == global_batch, w
        losses.append(float(jax.device_get(metrics["loss_sum"])) / w)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses

    # the loss is a global quantity: both ranks must agree bit-for-bit
    all_losses = np.asarray(host_all_gather(np.float32(losses[-1])))
    assert np.all(all_losses == all_losses.ravel()[0]), all_losses

    barrier("end")
    cleanup_distributed()
    print(f"WORKER_OK rank={rank} loss={losses[-1]:.5f}", flush=True)


if __name__ == "__main__":
    main()
