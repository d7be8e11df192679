"""The step builders a traffic mix can name, each through the product's API.

``build(name, ...)`` returns ``(step, params)``: ``step()`` dispatches one
optimizer step on this rank's batch and returns the loss as a device array
without waiting for it; ``params()`` returns the current parameters as this
process's local arrays.  ``hvd.init()`` must have run.

Host spans (``jax.profiler.TraceAnnotation``) wrap each call into a layer, so
a traced run can say what the host was doing while the device idled.  They
cost about a microsecond when no trace is on.
"""

from __future__ import annotations

import jax
import optax
from jax.profiler import TraceAnnotation as span

from chip_bench import reference


def _eager(config, tx, params, aux, batch):
    """``hvd.DistributedOptimizer``: jitted backward, the framework's update
    (fuse -> negotiate -> XLA allreduce -> unfuse -> optax), jitted apply.

    A corrected copy of ``examples/jax/jax_synthetic_benchmark.py``'s eager
    branch: there ``optax.apply_updates`` runs un-jitted, one dispatched add
    per parameter leaf, and held 64-72% of the device's idle time (ledger,
    PR 22).  ``DistributedOptimizer`` keeps every pure piece of the update
    under jit; so does this loop."""
    import horovod_tpu as hvd

    dopt = hvd.DistributedOptimizer(tx)
    opt_state = dopt.init(params)

    grad_step = reference.make_grad(config)
    apply_updates = jax.jit(optax.apply_updates, donate_argnums=(0,))

    def step():
        nonlocal params, aux, opt_state
        with span("grad_step"):
            (loss, aux), grads = grad_step(params, aux, batch)
        with span("dopt.update"):
            updates, opt_state = dopt.update(grads, opt_state, params)
        with span("apply_updates"):
            params = apply_updates(params, updates)
        return loss

    return step, lambda: params


def _wfbp(config, tx, params, aux, batch):
    """``hvd.make_overlapped_train_step``: one XLA program over the process
    mesh; the runtime's negotiation and fusion are bypassed."""
    import horovod_tpu as hvd

    def loss(params, aux, batch):
        return config.loss(params, aux, batch)

    wstep = hvd.make_overlapped_train_step(loss, tx, has_aux=True)
    params, opt_state, aux = wstep.init(params, jax.jit(tx.init)(params),
                                        aux)

    def step():
        nonlocal params, aux, opt_state
        with span("wfbp_step"):
            params, opt_state, aux, loss = wstep(params, opt_state, batch,
                                                 aux)
        return loss

    return step, lambda: wstep.fetch(params)


BUILDERS = {"eager": _eager, "wfbp": _wfbp}
# The host spans the builders write, for the attribution of idle gaps.
SPANS = ("grad_step", "dopt.update", "apply_updates", "wfbp_step",
         "loss_read")


def build(name, config, tx, params, aux, batch):
    try:
        builder = BUILDERS[name]
    except KeyError:
        raise SystemExit(f"chip_bench: no step builder {name!r}; "
                         f"have {sorted(BUILDERS)}") from None
    return builder(config, tx, params, aux, batch)
