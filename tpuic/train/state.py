"""Train state: params + batch_stats + optimizer state + step.

The reference's mutable training state is spread across the DDP module's
parameters, BN running stats buried in module buffers, replicated Adam state,
and Python-side ``start_epoch``/``best_score`` (train.py:127-150). Here it is
one immutable pytree, which is what makes sharding, donation, and
checkpointing uniform.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.core import unfreeze


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: optax.OptState
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    # Exponential moving average of params (OptimConfig.ema_decay > 0);
    # None disables — an empty pytree subtree, so shardings, donation, and
    # checkpoints are unaffected when off.
    ema_params: Any = None
    # Consecutive non-finite (skipped) steps, maintained IN-GRAPH by the
    # train step's guard (OptimConfig.skip_nonfinite): 0 after every
    # applied update, +1 per skip. Living in the state keeps the streak
    # exact with zero extra host syncs — the Trainer reads it through the
    # same deferred metrics drain as loss, and rolls back past
    # RunConfig.skip_threshold. None on states built by older callers;
    # the guard then still skips, it just can't count streaks.
    skip_count: Any = None

    @property
    def inference_params(self):
        """The weights evaluation/inference should score: the EMA when the
        recipe maintains one, else the raw params. The single source of
        truth for eval_step, predict, and best-checkpoint selection."""
        return self.ema_params if self.ema_params is not None else self.params

    def apply_gradients(self, grads) -> "TrainState":
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(step=self.step + 1, params=new_params,
                            opt_state=new_opt_state)


def opt_state_bytes(state: TrainState) -> int:
    """GLOBAL byte size of the optimizer state (every array leaf's full
    logical extent) — the denominator of the ZeRO 1/R memory claim."""
    return sum(int(leaf.nbytes)
               for leaf in jax.tree_util.tree_leaves(state.opt_state)
               if hasattr(leaf, "nbytes"))


def opt_state_device_bytes(state: TrainState,
                           device: jax.Device) -> int:
    """Bytes of optimizer state RESIDENT on ``device`` — per-shard, not
    logical: a leaf sharded over the ``data`` axis (ZeRO-1,
    tpuic/parallel/sharding.py) charges ``nbytes / R`` here while a
    replicated leaf charges its full size (optimizer memory per replica
    ~ 1/R)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        if not isinstance(leaf, jax.Array):
            continue
        for shard in leaf.addressable_shards:
            if shard.device == device:
                total += int(shard.data.nbytes)
    return total


def create_train_state(model, tx: optax.GradientTransformation, rng: jax.Array,
                       input_shape, train: bool = True,
                       ema: bool = False) -> TrainState:
    """Initialize params/batch_stats with a dummy batch of ``input_shape``.

    The batch dim is forced to 1: param shapes don't depend on it, and a
    global-batch-sized unsharded dummy would OOM device 0 at pod scale.
    ``ema=True`` seeds ema_params = params (no debias term needed).
    """
    dummy = jnp.zeros((1,) + tuple(input_shape[1:]), jnp.float32)
    # Init in train mode so branches that only exist then (inception aux head,
    # drop-path) create their params too; eval-only applies just ignore them.
    params_rng, dropout_rng = jax.random.split(rng)
    variables = model.init({"params": params_rng, "dropout": dropout_rng},
                           dummy, train=True)
    # Plain dicts throughout: model.apply(mutable=...) returns plain dicts in
    # current flax, and jit out_shardings prefix trees must match container
    # types exactly.
    params = unfreeze(variables.get("params", {}))
    batch_stats = unfreeze(variables.get("batch_stats", {}))
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
        apply_fn=model.apply,
        tx=tx,
        # A REAL copy: sharing params' buffers would double-donate them
        # under the jitted step's donate_argnums and wedge the executable.
        ema_params=(jax.tree.map(lambda x: jnp.array(x, copy=True), params)
                    if ema else None),
        skip_count=jnp.zeros((), jnp.int32),
    )
