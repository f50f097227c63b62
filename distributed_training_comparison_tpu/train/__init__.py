"""Training runtime: optimizer, state, compiled steps, Trainer, checkpoints.

Parity target: reference ``src/{single,dp,ddp}/trainer.py`` — ``Trainer``
with ``fit`` / ``validate`` / ``test`` / ``configure_optimizers`` /
``save_checkpoint``, AMP, versioned best-checkpointing, TensorBoard + file
logging (SURVEY.md §2.1 #5-6).

TPU-native redesign: the hot path is a pure function
``(state, batch, key) -> (state, metrics)`` compiled once by XLA over the
device mesh; a whole epoch runs as a ``lax.scan`` with the dataset resident
in HBM, so the host does no per-step work at all (the reference pays a
python-loop iteration + H2D copy + ``loss.item()`` device sync every step,
``src/single/trainer.py:126-153``).  Single/dp/ddp/multi-host are the same
compiled program on different mesh shapes.
"""

from .optim import configure_optimizers, step_lr_schedule
from .state import TrainState, create_train_state
from .step import (
    make_train_step,
    make_eval_step,
    make_eval_runner,
    make_chunk_runner,
    make_device_chunk_runner,
)
from .async_ckpt import AsyncCheckpointer
from .checkpoint import (
    agreed_version_dir,
    find_valid_resume,
    find_version_dir,
    find_serving_checkpoint,
    save_checkpoint,
    load_checkpoint,
    load_eval_variables,
    save_resume_state,
    load_resume_state,
)
from .trainer import Trainer

__all__ = [
    "configure_optimizers",
    "step_lr_schedule",
    "TrainState",
    "create_train_state",
    "make_train_step",
    "make_chunk_runner",
    "make_device_chunk_runner",
    "make_eval_step",
    "make_eval_runner",
    "AsyncCheckpointer",
    "agreed_version_dir",
    "find_valid_resume",
    "find_version_dir",
    "find_serving_checkpoint",
    "save_checkpoint",
    "load_checkpoint",
    "load_eval_variables",
    "save_resume_state",
    "load_resume_state",
    "Trainer",
]
