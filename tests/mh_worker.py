"""Multi-host worker: one JAX process of a 2-process CPU 'cluster'.

Launched by tests/test_multihost.py with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the pair forms an
8-device, 2-process mesh — the CI analogue of two TPU hosts over DCN.  This
executes the ``jax.process_count() > 1`` branches that single-process tests
can never reach (the reference never tests multi-node at all, SURVEY.md §4):

- ``parallel.init_distributed`` → ``jax.distributed.initialize`` rendezvous
  (the ``dist.init_process_group`` analogue, ``src/ddp/main.py:18-23``),
- ``place_tree``/``put_replicated`` global assembly from per-process hosts,
- ``shard_batch`` per-process contribution to a global batch,
- one SPMD train step whose gradient all-reduce crosses 'hosts',
- the ``test()``-style best-checkpoint broadcast: process-0 value →
  ``broadcast_one_to_all`` → re-place.

Prints one ``RESULT`` line the parent asserts on (loss equality across
processes proves the collective actually synchronized them).
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")  # CPU-only worker, also when run by hand

import flax.linen as nn  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


class TinyNet(nn.Module):
    num_classes: int = 10

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(8, (3, 3), strides=2, use_bias=False)(x)
        x = nn.BatchNorm(use_running_average=not train)(x)
        x = nn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


def main(rank: int, port: int) -> None:
    from distributed_training_comparison_tpu import parallel
    from distributed_training_comparison_tpu.train import (
        configure_optimizers,
        create_train_state,
        make_train_step,
    )

    class HP:
        world_size = 2
        dist_url = f"127.0.0.1:{port}"
        lr = 0.05
        weight_decay = 1e-4
        lr_decay_step_size = 25
        lr_decay_gamma = 0.1

    HP.rank = rank
    parallel.init_distributed(HP)
    assert jax.process_count() == 2, jax.process_count()
    assert jax.process_index() == rank
    assert len(jax.devices()) == 8 and len(jax.local_devices()) == 4

    mesh = parallel.make_mesh(backend="ddp")
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    state = create_train_state(TinyNet(), jax.random.key(0), tx)
    sharding = parallel.state_shardings(mesh, state)
    state = parallel.place_tree(state, sharding)  # multi-host assembly branch

    # per-process half of a global batch of 32 — both processes build the
    # same global data, each contributes its slice (DistributedSampler
    # analogue; see parallel/sharding.py shard_batch)
    rng = np.random.default_rng(0)
    gx = rng.normal(size=(32, 32, 32, 3)).astype(np.float32)
    gy = rng.integers(0, 10, size=(32,)).astype(np.int32)
    half = 16
    lx, ly = gx[rank * half : (rank + 1) * half], gy[rank * half : (rank + 1) * half]
    bx, by = parallel.shard_batch((lx, ly), mesh)
    assert bx.shape == (32, 32, 32, 3), bx.shape  # global shape, not local

    step = make_train_step(mesh, augment=False, state_sharding=sharding)
    state, metrics = step(state, bx, by, jax.random.key(1))
    loss = float(metrics["loss"])  # replicated global scalar

    # fetch_to_host: multi-host replicated leaves take the collective-free
    # local-read path (safe from one process alone); partitioned leaves take
    # the symmetric all-gather path — both must return the global value
    from distributed_training_comparison_tpu.parallel.sharding import (
        fetch_to_host,
        needs_collective_fetch,
    )

    host_params = fetch_to_host(state.params)  # replicated → local read
    for leaf in jax.tree_util.tree_leaves(host_params):
        assert isinstance(leaf, np.ndarray)
    gvals = np.arange(32, dtype=np.float32)
    sharded = parallel.shard_batch(gvals.reshape(2, 16)[rank], mesh)
    assert needs_collective_fetch(sharded) and not needs_collective_fetch(
        host_params
    )
    gathered = fetch_to_host(sharded)  # partitioned → all-gather, symmetric
    assert np.array_equal(gathered, gvals), gathered

    # chunked host-streaming layout (K, B, ...) assembles across processes
    # with the batch on axis 1 (shard_batch(batch_axis=1) multi-host branch)
    gchunk = np.arange(2 * 32, dtype=np.float32).reshape(2, 32)
    local_chunk = gchunk[:, rank * 16 : (rank + 1) * 16]
    chunk_arr = parallel.shard_batch(local_chunk, mesh, batch_axis=1)
    assert chunk_arr.shape == (2, 32), chunk_arr.shape
    assert np.array_equal(fetch_to_host(chunk_arr), gchunk)

    # the test() broadcast pattern (train/trainer.py): process-0's params win
    from jax.experimental import multihost_utils

    local_params = jax.device_get(state.params)
    if rank != 0:
        local_params = jax.tree_util.tree_map(lambda a: a * 0.0, local_params)
    synced = multihost_utils.broadcast_one_to_all(local_params)
    placed = parallel.place_tree(synced, sharding.params)
    # broadcast restored process-0's (trained, nonzero) values everywhere
    l2 = sum(
        float(jnp.sum(jnp.abs(x))) for x in jax.tree_util.tree_leaves(placed)
    )
    assert l2 > 0.0, "broadcast lost process-0 params"

    print(
        f"RESULT rank={rank} procs={jax.process_count()} "
        f"loss={loss:.6f} step={int(jax.device_get(state.step))} l2={l2:.4f}",
        flush=True,
    )


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]))
