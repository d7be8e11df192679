"""Sharded train-step builders: models × parallel layer × optax.

Two execution modes, matching the two halves of the framework:

1. **GSPMD mode** (`make_sharded_train_step`) — one ``jit`` over the whole
   step with NamedShardings: batch sharded on ``data``, params sharded per
   their ``nn.with_partitioning`` metadata (TP on ``model``).  XLA inserts
   every collective: DP gradient allreduce (the reference's entire product,
   `torch/optimizer.py:32`), TP psums, and BatchNorm statistics over the
   *global* batch — SyncBatchNorm (reference `sync_batch_norm.py`) for
   free.

2. **Manual mode** (`make_seq_parallel_train_step`) — ``shard_map`` with
   the ``seq`` axis bound, for ring/Ulysses long-context models where the
   attention itself is a collective algorithm.  Gradients are explicitly
   pmean'd over (data, seq) — the `allreduce_gradients` path.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.timeline import scope
from ..parallel.grad_sync import allreduce_gradients
from ..parallel.sharding import shard_map_fn


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any = None


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy; accepts [..., C] logits + [...] int labels."""
    logits = logits.astype(jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.reshape(-1, logits.shape[-1]), labels.reshape(-1)).mean()


def _unbox(tree):
    """Strip flax Partitioned boxes → raw arrays."""
    return jax.tree_util.tree_map(
        lambda x: x.unbox() if isinstance(x, nn.Partitioned) else x, tree,
        is_leaf=lambda x: isinstance(x, nn.Partitioned))


def param_specs(boxed_params) -> Any:
    """PartitionSpecs from ``nn.with_partitioning`` metadata (replicated for
    unannotated leaves)."""
    return nn.get_partition_spec(boxed_params)


def create_train_state(model: nn.Module, rng, sample_input, tx,
                       mesh: Optional[Mesh] = None,
                       init_kwargs: Optional[dict] = None) -> TrainState:
    """Initialize params (+ batch_stats) and optimizer state; when ``mesh``
    is given, place every leaf according to its partitioning annotation —
    the SPMD analog of rank-0-init + `broadcast_parameters`
    (reference `torch/functions.py:30`)."""
    variables = model.init(rng, sample_input, **(init_kwargs or {}))
    boxed = variables["params"]
    specs = param_specs(boxed)
    params = _unbox(boxed)
    batch_stats = variables.get("batch_stats")
    if mesh is not None:
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs)
        if batch_stats is not None:
            batch_stats = jax.device_put(
                batch_stats, NamedSharding(mesh, P()))
        # Build opt_state under jit so GSPMD shards its moment buffers like
        # their params — otherwise the first train step's output shardings
        # differ from its inputs and the second call recompiles.
        opt_state = jax.jit(tx.init)(params)
    else:
        opt_state = tx.init(params)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt_state, batch_stats=batch_stats)


def make_sharded_train_step(model: nn.Module, tx,
                            mesh: Optional[Mesh] = None,
                            loss_fn: Callable = cross_entropy_loss,
                            has_batch_stats: bool = False,
                            model_kwargs: Optional[dict] = None,
                            donate: bool = True):
    """GSPMD train step: ``train_step(state, batch) -> (state, loss)``.

    ``batch`` is ``{'x': inputs, 'y': integer labels}``.  Callers place
    ``batch`` with :func:`horovod_tpu.parallel.shard_batch` and ``state``
    via :func:`create_train_state`; jit propagates those shardings.
    """
    kwargs = model_kwargs if model_kwargs is not None else {"train": True}

    def step(state: TrainState, batch) -> tuple:
        def loss(params):
            variables = {"params": params}
            with scope("loss"):
                if has_batch_stats:
                    variables["batch_stats"] = state.batch_stats
                    logits, updated = model.apply(
                        variables, batch["x"], mutable=["batch_stats"],
                        **kwargs)
                    return loss_fn(logits, batch["y"]), \
                        updated["batch_stats"]
                logits = model.apply(variables, batch["x"], **kwargs)
                return loss_fn(logits, batch["y"]), None

        (loss_val, new_stats), grads = jax.value_and_grad(
            loss, has_aux=True)(state.params)
        with scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt,
                                  batch_stats=new_stats if has_batch_stats
                                  else state.batch_stats)
        return new_state, loss_val

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_seq_parallel_train_step(model: nn.Module, tx, mesh: Mesh,
                                 data_axis: str = "data",
                                 seq_axis: str = "seq",
                                 donate: bool = True):
    """shard_map train step for ring/Ulysses models:
    ``train_step(state, tokens, targets) -> (state, loss)``.

    ``tokens``/``targets`` are ``[batch, seq]`` int arrays, batch split over
    ``data_axis`` and sequence over ``seq_axis``; params replicated.
    """
    axes = (data_axis, seq_axis)

    def local_step(state: TrainState, tokens, targets):
        def loss(params):
            with scope("loss"):
                logits = model.apply({"params": params}, tokens)
                return cross_entropy_loss(logits, targets)

        loss_val, grads = jax.value_and_grad(loss)(state.params)
        # Params are replicated: average grads and loss across every shard.
        with scope("allreduce"):
            grads = allreduce_gradients(grads, axis_name=axes, op="average")
            loss_val = jax.lax.pmean(loss_val, axes)
        with scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        return (state.replace(step=state.step + 1, params=new_params,
                              opt_state=new_opt), loss_val)

    tok_spec = P(data_axis, seq_axis)
    mapped = shard_map_fn(
        local_step, mesh,
        in_specs=(P(), tok_spec, tok_spec),
        out_specs=(P(), P()))
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())
