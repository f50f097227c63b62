"""Optimizer and LR schedule with exact torch-semantics parity.

Parity: reference ``configure_optimizers`` — ``SGD(lr, momentum=0.9,
weight_decay, nesterov=True)`` + ``StepLR(step_size, gamma)`` stepped once
per **epoch** (``src/single/trainer.py:78-94,120``).

Semantics that must match for the accuracy target (SURVEY.md §7 risks):

- torch couples weight decay into the gradient *before* the momentum buffer
  (``d_p = grad + wd*p``; buf = m*buf + d_p) and applies it to **every**
  parameter including BN scale/bias → ``optax.add_decayed_weights`` ahead of
  the momentum transform, no mask.
- torch nesterov: ``update = d_p + m*buf`` → ``optax.trace(decay=m,
  nesterov=True)`` computes exactly this.
- StepLR multiplies lr by ``gamma`` every ``step_size`` epochs, constant
  within an epoch → a staircase schedule over the global step with
  ``transition_steps = step_size * steps_per_epoch``.

The schedule is part of the compiled update (a function of ``opt_state``'s
step count), so LR changes never require retracing or host intervention —
unlike the reference's host-side ``lr_scheduler.step()``.

Sharding note (``parallel/comms.py`` ``--shard-optim``): this transform
chain is ELEMENTWISE over parameters — decay couple, momentum trace, and
schedule scale never mix values across parameters or across elements of
one parameter — which is what makes the ZeRO cross-replica sharded update
exact: a per-shard optimizer step over a data-sharded gradient computes
the same values the replicated step would, so sharding is purely a layout
choice (pinned at ~1 ulp by ``tests/test_comms.py``).  A future
non-elementwise transform (cross-leaf global-norm clipping, LAMB trust
ratios) stays *correct* under GSPMD — XLA inserts the cross-shard
reductions the math needs — but turns the free layout change into real
collectives; price it against the compile ledger before defaulting it.
"""

from __future__ import annotations

import jax
import optax

# AdamW (Loshchilov & Hutter 2019) as language models are trained with it:
# beta 0.9 / 0.95, eps 1e-8; --lr and --weight-decay are the launcher's
ADAMW_B1, ADAMW_B2, ADAMW_EPS = 0.9, 0.95, 1e-8


def step_lr_schedule(
    base_lr: float, step_size_epochs: int, gamma: float, steps_per_epoch: int
) -> optax.Schedule:
    """StepLR as a staircase over global steps."""
    return optax.exponential_decay(
        init_value=base_lr,
        transition_steps=max(1, step_size_epochs * steps_per_epoch),
        decay_rate=gamma,
        staircase=True,
    )


def configure_optimizers(
    hparams, steps_per_epoch: int
) -> tuple[optax.GradientTransformation, optax.Schedule]:
    """Build the torch-parity SGD+StepLR transform, or ``--optimizer
    adamw`` under the same schedule (constant with ``--lr-decay-gamma 1``).

    Returns ``(tx, schedule)``; the schedule is also returned standalone so
    the Trainer can log the current LR without peeking into opt_state
    (reference logs ``optimizer.param_groups[0]['lr']``,
    ``src/single/trainer.py:159``).
    """
    schedule = step_lr_schedule(
        hparams.lr,
        hparams.lr_decay_step_size,
        hparams.lr_decay_gamma,
        steps_per_epoch,
    )
    if getattr(hparams, "optimizer", "sgd") == "adamw":
        # decoupled decay on matrices only (two axes or more: projections,
        # expert stacks, the embedding), never on a norm's scale.  Still
        # elementwise, so the sharded update's precondition above holds.
        tx = optax.adamw(
            schedule, b1=ADAMW_B1, b2=ADAMW_B2, eps=ADAMW_EPS,
            weight_decay=hparams.weight_decay,
            mask=lambda params: jax.tree_util.tree_map(
                lambda p: p.ndim >= 2, params
            ),
        )
        return tx, schedule
    tx = optax.chain(
        optax.add_decayed_weights(hparams.weight_decay),
        optax.sgd(learning_rate=schedule, momentum=0.9, nesterov=True),
    )
    return tx, schedule
