"""WFBP overlap tests: microbatch-pipelined enqueue + in-program step.

Reference analog: WFBP hook scheduling in ``torch/optimizer.py:103-149``,
verified there by ``test/parallel/test_torch.py`` gradient-equivalence
cases.  Here: (a) overlap=True is bit-equivalent to accumulate-then-reduce
(linearity), (b) the compiled overlapped step trains identically to
single-process training on the concatenated batch (sync-DP equivalence),
(c) misuse raises.
"""


import numpy as np
import pytest

from .helpers import run_distributed


def _xla_env() -> dict:
    from .helpers import reserve_port

    port = reserve_port()
    return {
        "HOROVOD_DATA_PLANE": "xla",
        "HOROVOD_JAX_COORDINATOR": f"127.0.0.1:{port}",
    }


def test_overlap_requires_multiple_backward_passes():
    import optax

    from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer

    with pytest.raises(ValueError, match="backward_passes_per_step"):
        DistributedOptimizer(optax.sgd(0.1), overlap=True)
    with pytest.raises(ValueError, match="Adasum"):
        DistributedOptimizer(optax.sgd(0.1), op="adasum",
                             backward_passes_per_step=2, overlap=True)


def test_overlap_matches_accumulate_two_ranks():
    """overlap=True and the plain bpps path produce identical updates:
    allreduce is linear, so reduce-every-microbatch == reduce-the-sum."""
    out = run_distributed(2, """
import jax
import jax.numpy as jnp
import optax
from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer

params = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
          "b": jnp.ones(3, jnp.float32)}
# rank-dependent microbatch gradients
def g(mb):
    return {"w": jnp.full((2, 3), float(rank + 1 + mb)),
            "b": jnp.full(3, float(10 * rank + mb))}

results = {}
for overlap in (False, True):
    tx = optax.sgd(0.1, momentum=0.9)
    dopt = DistributedOptimizer(tx, backward_passes_per_step=3,
                                overlap=overlap)
    st = dopt.init(params)
    p = params
    for step in range(2):          # two full accumulation windows
        for mb in range(3):
            upd, st = dopt.update(g(mb), st, p)
            p = optax.apply_updates(p, upd)
    results[overlap] = p

for k in results[False]:
    a = np.asarray(results[False][k])
    b = np.asarray(results[True][k])
    assert np.allclose(a, b, atol=1e-6), (k, a, b)
print("OVERLAP_EQ_OK", rank, flush=True)
""", timeout=240)
    for r, o in enumerate(out):
        assert f"OVERLAP_EQ_OK {r}" in o


def test_overlapped_step_single_process():
    """np=1 smoke: the compiled overlapped step runs, loss decreases, and
    matches plain optax exactly (size-1 mesh, allreduce is identity)."""
    out = run_distributed(1, """
import jax
import jax.numpy as jnp
import optax
from horovod_tpu.frameworks.jax.wfbp import make_overlapped_train_step

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)

rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.randn(4, 2), jnp.float32)}
tx = optax.sgd(0.05)
batches = [{"x": jnp.asarray(rng.randn(8, 4), jnp.float32),
            "y": jnp.asarray(rng.randn(8, 2), jnp.float32)}
           for _ in range(5)]

step = make_overlapped_train_step(loss_fn, tx)
p, s = step.init(params, tx.init(params))
losses = []
for b in batches:
    p, s, loss = step(p, s, b)
    losses.append(float(np.asarray(loss)))
assert losses[-1] < losses[0], losses

# exact match vs plain optax
p2, s2 = params, tx.init(params)
fn = jax.jit(lambda p, s, b: (lambda l, g: (optax.apply_updates(
    p, tx.update(g, s, p)[0]), tx.update(g, s, p)[1], l))(
    *jax.value_and_grad(loss_fn)(p, b)))
for b in batches:
    p2, s2, _ = fn(p2, s2, b)
got = np.asarray(step.fetch(p)["w"])
exp = np.asarray(p2["w"])
assert np.allclose(got, exp, atol=1e-6), (got, exp)
print("WFBP_STEP_OK", rank, flush=True)
""", timeout=240)
    assert "WFBP_STEP_OK 0" in out[0]


def test_overlapped_step_has_aux():
    """Aux state (flax batch_stats shape) threads through the compiled
    step and matches a hand-rolled update."""
    out = run_distributed(1, """
import jax
import jax.numpy as jnp
import optax
from horovod_tpu.frameworks.jax.wfbp import make_overlapped_train_step

def loss_fn(p, aux, b):
    pred = b["x"] @ p["w"]
    new_aux = {"ema": 0.9 * aux["ema"] + 0.1 * jnp.mean(pred)}
    return jnp.mean((pred - b["y"]) ** 2), new_aux

rng = np.random.RandomState(1)
params = {"w": jnp.asarray(rng.randn(3, 2), jnp.float32)}
aux = {"ema": jnp.zeros(())}
tx = optax.sgd(0.1)
step = make_overlapped_train_step(loss_fn, tx, has_aux=True)
p, s, a = step.init(params, tx.init(params), aux)
b = {"x": jnp.asarray(rng.randn(4, 3), jnp.float32),
     "y": jnp.asarray(rng.randn(4, 2), jnp.float32)}
for _ in range(3):
    p, s, a, loss = step(p, s, b, a)

# manual reference
p2, a2, s2 = params, aux, tx.init(params)
for _ in range(3):
    (l, a2), g = jax.value_and_grad(loss_fn, has_aux=True)(p2, a2, b)
    upd, s2 = tx.update(g, s2, p2)
    p2 = optax.apply_updates(p2, upd)
assert np.allclose(np.asarray(step.fetch(p)["w"]), np.asarray(p2["w"]),
                   atol=1e-6)
assert np.allclose(np.asarray(step.fetch(a)["ema"]),
                   np.asarray(a2["ema"]), atol=1e-6)
print("WFBP_AUX_OK", rank, flush=True)
""", timeout=240)
    assert "WFBP_AUX_OK 0" in out[0]


@pytest.mark.parametrize("caller_keeps", [True, False],
                         ids=["caller_keeps_its_trees", "caller_lets_go"])
def test_init_copies_only_what_the_caller_still_holds(caller_keeps):
    """np=1: ``init`` hands back arrays that share the caller's buffers and
    the copy waits for the first call.  A caller that still holds its trees
    then gets them copied (the step donates the copies: the caller's arrays
    stay whole and unchanged); one that let go has nothing copied, so
    weights and optimizer state are never on the device twice."""
    out = run_distributed(1, f"""
import gc
import jax
import jax.numpy as jnp
import optax
from horovod_tpu.frameworks.jax.wfbp import make_overlapped_train_step

def loss_fn(p, aux, b):
    return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), aux + 1

rng = np.random.RandomState(0)
params = {{"w": jnp.asarray(rng.randn(4, 2), jnp.float32)}}
before = np.asarray(params["w"]).copy()
tx = optax.adam(0.05)
b = {{"x": jnp.asarray(rng.randn(8, 4), jnp.float32),
     "y": jnp.asarray(rng.randn(8, 2), jnp.float32)}}
step = make_overlapped_train_step(loss_fn, tx, has_aux=True)
state, aux = tx.init(params), jnp.zeros((), jnp.int32)
p, s, a = step.init(params, state, aux)
borrowed = len(step._borrowed)
assert borrowed == 1 + 3 + 1, borrowed        # w; count, mu, nu; aux
assert p["w"].unsafe_buffer_pointer() == params["w"].unsafe_buffer_pointer()
pointer = p["w"].unsafe_buffer_pointer()
handed = []
own = step._own
def watched(*trees):
    out = own(*trees)
    handed.append(out[0]["w"].unsafe_buffer_pointer())
    return out
step._own = watched
if not {caller_keeps}:
    del params, state, aux
    gc.collect()
for _ in range(3):
    p, s, a, loss = step(p, s, b, a)
assert step._borrowed == [] and len(handed) == 1
if {caller_keeps}:
    # The step got a copy; the caller's array is whole and as it was.
    assert handed[0] != pointer
    assert np.array_equal(np.asarray(params["w"]), before)
    assert not params["w"].is_deleted()
else:
    assert handed[0] == pointer
assert int(step.fetch(a)) == 3
assert not np.array_equal(np.asarray(step.fetch(p)["w"]), before)
print("WFBP_OWN_OK", rank, flush=True)
""", timeout=240)
    assert "WFBP_OWN_OK 0" in out[0]


def test_overlapped_step_matches_big_batch_two_ranks():
    """Sync-DP equivalence: two ranks on half-batches through the
    overlapped step == one process on the full batch.  The in-program
    allreduce must therefore compute the exact global-mean gradient."""
    out = run_distributed(2, """
import jax
import jax.numpy as jnp
import optax
from horovod_tpu.backend.xla import context
from horovod_tpu.frameworks.jax.wfbp import make_overlapped_train_step
assert context().ready, "XLA data plane required"

def loss_fn(params, batch):
    pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
    return jnp.mean((pred - batch["y"]) ** 2)

rng = np.random.RandomState(7)
params = {"w1": jnp.asarray(rng.randn(4, 8) * 0.3, jnp.float32),
          "w2": jnp.asarray(rng.randn(8, 2) * 0.3, jnp.float32)}
X = rng.randn(4, 6, 4).astype(np.float32)   # [steps, global_batch, d]
Y = rng.randn(4, 6, 2).astype(np.float32)

tx = optax.sgd(0.1, momentum=0.9)
step = make_overlapped_train_step(loss_fn, tx)
p, s = step.init(params, tx.init(params))
lo = rank * 3
for i in range(4):
    b = {"x": jnp.asarray(X[i, lo:lo + 3]), "y": jnp.asarray(Y[i, lo:lo + 3])}
    p, s, loss = step(p, s, b)
got = {k: np.asarray(v) for k, v in step.fetch(p).items()}

# single-process reference on the full batch
p2, s2 = params, tx.init(params)
vg = jax.jit(jax.value_and_grad(loss_fn))
for i in range(4):
    _, g = vg(p2, {"x": jnp.asarray(X[i]), "y": jnp.asarray(Y[i])})
    upd, s2 = tx.update(g, s2, p2)
    p2 = optax.apply_updates(p2, upd)
for k in got:
    exp = np.asarray(p2[k])
    assert np.allclose(got[k], exp, atol=1e-5), (k, got[k], exp)
print("WFBP_DP_OK", rank, flush=True)
""", timeout=300, extra_env=_xla_env())
    for r, o in enumerate(out):
        assert f"WFBP_DP_OK {r}" in o


def test_overlapped_step_signature_divergence_raises():
    """A rank tracing a different program shape must fail loudly up front
    (the negotiation-plane signature check), not hang in the collective."""
    out = run_distributed(2, """
import jax.numpy as jnp
import optax
from horovod_tpu.backend.xla import context
from horovod_tpu.frameworks.jax.wfbp import make_overlapped_train_step
assert context().ready

def loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"]) ** 2)

w_cols = 2 if rank == 0 else 3        # divergent param shapes
params = {"w": jnp.ones((4, w_cols), jnp.float32)}
tx = optax.sgd(0.1)
step = make_overlapped_train_step(loss_fn, tx)
p, s = step.init(params, tx.init(params))
try:
    step(p, s, {"x": jnp.ones((2, 4), jnp.float32)})
except RuntimeError as e:
    assert "diverged" in str(e), e
    print("WFBP_SIG_OK", rank, flush=True)
else:
    print("WFBP_SIG_MISSED", rank, flush=True)
""", timeout=300, extra_env=_xla_env())
    for r, o in enumerate(out):
        assert f"WFBP_SIG_OK {r}" in o

@pytest.mark.smoke
def test_abandoned_window_drain_is_nonblocking(monkeypatch):
    """Evicting an abandoned overlap window must never block update()
    (ADVICE r4 medium): a handle that never completes is handed to the
    background drainer and force-discarded after its deadline — the
    training path returns immediately."""
    import time

    from horovod_tpu.frameworks.jax import ops, optimizer

    # A handle nobody will ever complete (the asymmetric-abandonment case).
    stuck = ops._handles.allocate()
    # And one already completed: the drainer must release it promptly.
    done = ops._handles.allocate()
    from horovod_tpu.core.tensor_queue import Status
    ops._handles.mark_done(done, Status.OK(), "result")

    t0 = time.monotonic()
    optimizer._drain_handles_async([stuck, done], timeout_s=1.5)
    assert time.monotonic() - t0 < 0.5, "drain hand-off must not block"

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with ops._handles._lock:
            gone = (stuck not in ops._handles._events
                    and done not in ops._handles._events)
        if gone:
            break
        time.sleep(0.2)
    with ops._handles._lock:
        assert stuck not in ops._handles._events, "stuck handle not discarded"
        assert done not in ops._handles._events, "done handle not released"
        assert stuck not in ops._handles._done
        assert done not in ops._handles._done

    # A callback that fires AFTER the discard must not resurrect the entry.
    ops._handles.mark_done(stuck, Status.OK(), "late")
    with ops._handles._lock:
        assert stuck not in ops._handles._done


@pytest.mark.smoke
def test_optimizer_instances_get_distinct_wire_names(monkeypatch):
    """Two DistributedOptimizer instances in one process must enqueue
    under distinct wire-name prefixes (ADVICE r4: identical names across
    instances break concurrent training states loudly)."""
    import jax.numpy as jnp
    import optax

    from horovod_tpu.frameworks.jax import ops, optimizer, wfbp

    recorded = []

    def fake_async(tensor, name=None, op=None, **kw):
        recorded.append(name)
        h = ops._handles.allocate()
        from horovod_tpu.core.tensor_queue import Status
        ops._handles.mark_done(h, Status.OK(), tensor)
        return h

    monkeypatch.setattr(wfbp.ops, "allreduce_async", fake_async)
    monkeypatch.setattr(optimizer.ops, "initialized", lambda: True)

    grads = {"w": jnp.ones((2, 2), jnp.float32)}
    names = {}
    for i in range(2):
        recorded.clear()
        d = optimizer.DistributedOptimizer(optax.sgd(0.1))
        st = d.init(grads)
        d.update(grads, st, grads)
        assert recorded, "no enqueue recorded"
        names[i] = set(recorded)
    assert names[0] and names[1]
    assert names[0].isdisjoint(names[1]), (names, "wire names collide "
                                           "across optimizer instances")


@pytest.mark.smoke
def test_timeout_scale_env_is_floor(monkeypatch):
    """HVD_TEST_TIMEOUT_SCALE is a FLOOR: a loaded bare host can scale
    past it (ADVICE r4 low — it used to be a fixed override)."""
    from . import helpers

    monkeypatch.setenv("HVD_TEST_TIMEOUT_SCALE", "3")
    monkeypatch.setattr(helpers.os, "getloadavg", lambda: (20.0, 0, 0))
    monkeypatch.setattr(helpers.os, "cpu_count", lambda: 2)
    assert helpers._timeout_scale() == 6.0  # load wins, capped at 6

    monkeypatch.setattr(helpers.os, "getloadavg", lambda: (0.0, 0, 0))
    assert helpers._timeout_scale() == 3.0  # floor wins on idle/containers

    monkeypatch.delenv("HVD_TEST_TIMEOUT_SCALE")
    assert helpers._timeout_scale() == 1.0
