"""Train state: one pytree carrying everything the compiled step updates.

The reference scatters mutable training state across the Trainer object
(model params inside ``nn.Module``, optimizer + scheduler objects, AMP
scaler, epoch/step counters — ``src/single/trainer.py:19-76``).  Here it is
a single immutable pytree — params, BatchNorm ``batch_stats``, optimizer
state, step — so the whole update is a pure function ``state -> state`` that
XLA compiles and the mesh shards; checkpointing is serializing one pytree.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import optax
from flax import core, struct

from .task import IMAGE_CLASSIFICATION, Task, task_of


class TrainState(struct.PyTreeNode):
    """Minimal SPMD train state (flax ``train_state.TrainState`` + BN stats).

    ``comms_residual`` is the compressed-gradient-sync error-feedback
    residual (``parallel/comms.py``): a params-shaped fp32 tree under
    ``--grad-comms fp16/int8``, ``None`` otherwise.  ``None`` is an empty
    pytree node, so the default state flattens to exactly the same leaves
    as before the field existed — the benign path's executables (and their
    compile-event fingerprints) are unchanged.  The residual is
    deliberately NOT checkpointed (``checkpoint._state_dict``): a resumed
    run restarts it at zero, costing at most one step's quantization
    error.
    """

    step: jax.Array
    params: core.FrozenDict[str, Any]
    batch_stats: core.FrozenDict[str, Any]
    opt_state: optax.OptState
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    comms_residual: Any = None
    # what the model family trains on (train/task.py): static, like
    # apply_fn, so every step and eval program built from a state finds it
    task: Task = struct.field(pytree_node=False, default=IMAGE_CLASSIFICATION)

    def apply_gradients(self, *, grads, batch_stats) -> "TrainState":
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            batch_stats=batch_stats,
            opt_state=new_opt_state,
        )


def create_train_state(
    model, rng: jax.Array, tx: optax.GradientTransformation, input_shape=(1, 32, 32, 3)
) -> TrainState:
    """Initialize params/BN stats (fp32) and optimizer state.

    Init runs in fp32 regardless of the model's compute dtype — parameters
    and BN statistics are always stored full-precision; only activations are
    bf16 under the mixed-precision policy (replaces AMP GradScaler state,
    ``src/single/main.py:14``).
    """
    import jax.numpy as jnp

    task = task_of(model)  # an image of ``input_shape``, or the task's own
    variables = model.init(rng, task.init_input(input_shape), train=False)
    params = variables["params"]
    # models without BatchNorm have no batch_stats collection
    batch_stats = variables.get("batch_stats", {})
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
        apply_fn=model.apply,
        tx=tx,
        task=task,
    )
