"""jax synthetic ResNet-50 benchmark — the native flavor.

Two modes:
- ``--mode eager``: the Horovod-style eager path (``hvd.allreduce`` of
  grads via ``DistributedOptimizer``) — any-tensor-any-time semantics, XLA
  data plane when launched with ``hvdrun --data-plane xla``.  The updates
  are applied through ``jax.jit(optax.apply_updates, donate_argnums=(0,))``:
  un-jitted, ``optax.apply_updates`` dispatches one add per parameter leaf,
  and those dispatches held 64-72% of the device's idle time in the eager
  cells of the benchmark that ran this script (ledger, PR 22).
- ``--mode spmd`` (default): the TPU-first path — one jit'd train step over
  the device mesh, gradient sync folded into the step as a psum (XLA fuses
  it with backprop; this is the configuration ``bench.py`` measures).
- ``--mode wfbp``: the overlapped eager path —
  ``hvd.make_overlapped_train_step`` compiles forward+backward+allreduce+
  update into one program over the runtime's process mesh; XLA overlaps
  the gradient collectives with backward (in-program WFBP).

Run: ``hvdrun -np 4 python examples/jax/jax_synthetic_benchmark.py --mode eager``
     ``hvdrun -np 4 python examples/jax/jax_synthetic_benchmark.py --mode wfbp``
     ``python examples/jax/jax_synthetic_benchmark.py  # single-process spmd``

On a TPU host ``hvdrun`` gives each process one chip and selects the XLA
data plane; anywhere else pass ``--data-plane xla`` (``wfbp`` needs it).
``chip_smoke.py`` drives :func:`build_step` for its eager and wfbp stages.
"""

import argparse
import time

import numpy as np


def build_step(mode, model, tx, images, labels):
    """Wire up one optimizer step in ``mode``; ``hvd.init()`` must have run.

    Returns ``(step, params)``: ``step()`` runs one step on this rank's
    ``images``/``labels`` and returns the loss; ``params()`` returns the
    current parameters as this process's local arrays."""
    import jax
    import optax

    from horovod_tpu.models.training import create_train_state

    rng = jax.random.PRNGKey(0)
    num_classes = model.num_classes

    if mode == "spmd":
        from horovod_tpu.models.training import make_sharded_train_step
        from horovod_tpu.parallel import MeshSpec, build_mesh, shard_batch

        mesh = build_mesh(MeshSpec(data=-1))
        state = create_train_state(model, rng, images, tx, mesh=mesh,
                                   init_kwargs={"train": True})
        step = make_sharded_train_step(model, tx, mesh,
                                       has_batch_stats=True, donate=True)
        batch = shard_batch(mesh, {"x": images, "y": labels})

        def benchmark_step():
            nonlocal state
            state, loss = step(state, batch)
            return loss

        return benchmark_step, lambda: state.params

    state = create_train_state(model, rng, images, tx,
                               init_kwargs={"train": True})
    if mode == "wfbp":
        from horovod_tpu.frameworks.jax.wfbp import make_overlapped_train_step

        def wfbp_loss(p, bstats, b):
            out, updates = model.apply(
                {"params": p, "batch_stats": bstats}, b["x"],
                train=True, mutable=["batch_stats"])
            one_hot = jax.nn.one_hot(b["y"], num_classes)
            return (optax.softmax_cross_entropy(out, one_hot).mean(),
                    updates["batch_stats"])

        wstep = make_overlapped_train_step(wfbp_loss, tx, has_aux=True)
        wp, ws, wa = wstep.init(state.params, tx.init(state.params),
                                state.batch_stats)
        wbatch = {"x": images, "y": labels}

        def benchmark_step():
            nonlocal wp, ws, wa
            wp, ws, wa, loss = wstep(wp, ws, wbatch, wa)
            return loss

        return benchmark_step, lambda: wstep.fetch(wp)

    from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer

    dopt = DistributedOptimizer(tx)
    opt_state = dopt.init(state.params)

    @jax.jit
    def grad_step(params, batch_stats):
        def loss_fn(p):
            out, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            one_hot = jax.nn.one_hot(labels, num_classes)
            return optax.softmax_cross_entropy(out, one_hot).mean(), updates
        (loss, updates), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, grads, updates["batch_stats"]

    # Commit the state to this process's device, where the allreduced
    # updates will live.  jit keys on committedness: a state that starts
    # uncommitted makes grad_step compile three times at np>1, once per
    # mix of committed and uncommitted arguments (PERF.md, PR 21).
    device = jax.local_devices()[0]
    params = jax.device_put(state.params, device)
    batch_stats = jax.device_put(state.batch_stats, device)
    apply_updates = jax.jit(optax.apply_updates, donate_argnums=(0,))

    def benchmark_step():
        nonlocal params, batch_stats, opt_state
        loss, grads, batch_stats = grad_step(params, batch_stats)
        # eager allreduce of the grad pytree (the Horovod path)
        updates, opt_state = dopt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return loss

    return benchmark_step, lambda: params


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--mode", default="spmd",
                        choices=["spmd", "eager", "wfbp"])
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-warmup-batches", type=int, default=2)
    parser.add_argument("--num-iters", type=int, default=3)
    parser.add_argument("--num-batches-per-iter", type=int, default=3)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50

    hvd.init()

    model = ResNet50(num_classes=1000)
    images = jnp.ones((args.batch_size, args.image_size, args.image_size, 3),
                      jnp.bfloat16)
    labels = jnp.zeros((args.batch_size,), jnp.int32)
    tx = optax.sgd(0.01 * hvd.size(), momentum=0.9)
    benchmark_step, _ = build_step(args.mode, model, tx, images, labels)

    def log(s):
        if hvd.rank() == 0:
            print(s, flush=True)

    log(f"mode={args.mode} batch={args.batch_size} ranks={hvd.size()} "
        f"devices={len(jax.local_devices())}")
    for _ in range(args.num_warmup_batches):
        jax.block_until_ready(benchmark_step())

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            loss = benchmark_step()
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        img_sec = args.batch_size * args.num_batches_per_iter / dt
        log(f"Iter #{i}: {img_sec:.1f} img/sec per rank")
        img_secs.append(img_sec)

    mean = float(np.mean(img_secs))
    total = np.asarray(hvd.allreduce(np.array([mean]), op=hvd.Sum,
                                     name="imgsec"))[0]
    log(f"Img/sec per rank: {mean:.1f}")
    log(f"Total img/sec on {hvd.size()} rank(s): {total:.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
