"""Train state: params + optimizer, the reference's ``model.parameters()`` ↔
``optimizer`` pair (``pytorch_multilayer_perceptron.py:93-96``), functional.

``make_optimizer`` covers the reference's optimizer vocabulary: SGD
(``pytorch_cnn.py:119`` lr=0.01, ``pytorch_multilayer_perceptron.py:96``
lr=0.03) and Adam (``pytorch_lstm.py:127`` lr=1e-3,
``pytorch_machine_translator.py:129``) — plus the training-scale knobs the
reference lacks: learning-rate schedules (warmup/cosine), global-norm
gradient clipping, and gradient accumulation (K microbatch grads averaged
into one update, so a per-chip-memory-bound batch can still train at the
large effective batch a pod would use).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import optax
from flax import struct

#: The named scope of the optimizer's work (``tx.update`` and the applied
#: updates) in every train step that updates parameters: this one and
#: ``parallel.zero``'s sharded updates. A scope is metadata on the compiled
#: instructions (the ``tf_op`` a device trace shows), not an instruction.
UPDATE_SCOPE = "train.update"


def make_schedule(
    learning_rate: float,
    schedule: str | None = None,
    *,
    warmup_steps: int = 0,
    total_steps: int | None = None,
    end_value: float = 0.0,
) -> float | optax.Schedule:
    """Learning-rate schedule: ``None``/``"constant"`` (the reference's fixed
    lr), ``"cosine"`` (cosine decay to ``end_value`` over ``total_steps``),
    or ``"warmup_cosine"`` (linear 0→lr over ``warmup_steps``, then cosine).
    """
    if schedule in (None, "constant"):
        if warmup_steps:
            return optax.linear_schedule(0.0, learning_rate, warmup_steps)
        return learning_rate
    if schedule == "cosine":
        if total_steps is None:
            raise ValueError("cosine schedule requires total_steps")
        if warmup_steps:  # cosine-with-warmup IS warmup_cosine; honor it
            schedule = "warmup_cosine"
        else:
            return optax.cosine_decay_schedule(
                learning_rate, total_steps, alpha=end_value / learning_rate
            )
    if schedule == "warmup_cosine":
        if total_steps is None:
            raise ValueError("warmup_cosine schedule requires total_steps")
        return optax.warmup_cosine_decay_schedule(
            0.0,
            learning_rate,
            warmup_steps,
            max(total_steps, warmup_steps + 1),
            end_value=end_value,
        )
    raise ValueError(f"unknown schedule {schedule!r}")


def make_optimizer(
    name: str = "adam",
    learning_rate: float | optax.Schedule = 1e-3,
    *,
    schedule: str | None = None,
    warmup_steps: int = 0,
    total_steps: int | None = None,
    grad_clip: float | None = None,
    accumulate_steps: int = 1,
    **kw,
) -> optax.GradientTransformation:
    """Optimizer with optional schedule, clipping, and accumulation.

    ``accumulate_steps=K`` wraps the chain in ``optax.MultiSteps``: K calls
    to ``update`` average their gradients and emit one real parameter update
    (zero updates in between), so ``fit`` needs no special handling — the
    effective batch is K × the loader batch.

    ZeRO-1 composition (``fit(dp_mode="zero1")``): the sharded update runs
    ``tx.update`` on each chip's 1/N gradient slice, so only elementwise
    chains compose — a ``grad_clip`` baked in HERE would clip by the
    shard-local norm, and ``accumulate_steps > 1`` keeps cross-element
    counters per shard. Pass ``grad_clip=`` to
    ``parallel.zero.make_zero1_step`` (a true global-norm clip via a scalar
    psum) and leave both knobs off the optimizer for that mode; see
    ``docs/PARALLELISM.md``.
    """
    if isinstance(learning_rate, (int, float)):
        lr = make_schedule(
            learning_rate,
            schedule,
            warmup_steps=warmup_steps,
            total_steps=total_steps,
        )
    else:
        if schedule is not None or warmup_steps:
            raise ValueError(
                "learning_rate is already a schedule callable; "
                "schedule/warmup_steps would be silently ignored"
            )
        lr = learning_rate
    name = name.lower()
    if name == "sgd":
        base = optax.sgd(lr, **kw)
    elif name == "adam":
        base = optax.adam(lr, **kw)
    elif name == "adamw":
        base = optax.adamw(lr, **kw)
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if grad_clip is not None:
        base = optax.chain(optax.clip_by_global_norm(grad_clip), base)
    if accumulate_steps > 1:
        base = optax.MultiSteps(base, every_k_schedule=accumulate_steps)
    return base


class TrainState(struct.PyTreeNode):
    """Carry for the jitted train step: params, opt state, step counter.

    A lean re-implementation of ``flax.training.train_state.TrainState`` kept
    first-party so the sharding rules in ``parallel`` can address it without
    version skew.
    """

    step: jax.Array | int
    params: Any
    opt_state: optax.OptState
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    @classmethod
    def create(cls, *, apply_fn, params, tx) -> "TrainState":
        return cls(
            step=0, params=params, opt_state=tx.init(params), apply_fn=apply_fn, tx=tx
        )

    @property
    def opt_state_bytes(self) -> int:
        """Logical (unsharded) optimizer-state size in bytes — the number
        ZeRO-1 divides by the data-axis size; for the per-chip footprint
        of a sharded state see ``parallel.zero.opt_state_bytes_per_chip``."""
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves(self.opt_state)
            if hasattr(leaf, "nbytes")
        )

    def apply_gradients(self, grads) -> "TrainState":
        with jax.named_scope(UPDATE_SCOPE):
            updates, new_opt = self.tx.update(grads, self.opt_state, self.params)
            return self.replace(
                step=self.step + 1,
                params=optax.apply_updates(self.params, updates),
                opt_state=new_opt,
            )
