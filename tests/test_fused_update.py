"""``DistributedOptimizer.update`` feeds the reduced gradient to the
optimizer's program as its fused per-dtype buffers (ISSUE 26).

The tree path stays in the tree as the reference: ``wfbp.wait_tree`` (one
array per leaf) and a jitted ``tx.update`` on that tree, composed in the
worker exactly as ``update`` composed them before.  Every case holds the
buffer path to the same bits, step after step, on ``(updates, new
state)``.  One worker per world runs every case and prints a report; the
cases below each assert one fact of it.
"""

import json

import numpy as np
import pytest

from .helpers import reserve_port, run_distributed

WORKER = """
import json
import jax, jax.numpy as jnp, optax
from horovod_tpu.core.timeline import phase_stats
from horovod_tpu.frameworks.jax import wfbp
from horovod_tpu.frameworks.jax.compression import Compression

AVG = hvd.Average

def f32_tree(step, mb=0):
    k = float(1 + rank + 3 * step + 7 * mb)
    return {"w1": jnp.linspace(-1.0, 1.0, 32).reshape(4, 8) * k,
            "b1": jnp.arange(8, dtype=jnp.float32) / (3.0 * k),
            "w2": jnp.cos(jnp.arange(16, dtype=jnp.float32)).reshape(8, 2) / k,
            "b2": jnp.full((2,), 0.37 * k)}

def mixed_tree(step, mb=0):
    k = float(1 + rank + 3 * step)
    return {"a16": (jnp.linspace(-2.0, 2.0, 12).reshape(3, 4) * k
                    ).astype(jnp.bfloat16),
            "w": jnp.linspace(0.0, 1.0, 10).reshape(2, 5) / k,
            "s": jnp.float32(0.123 * k),
            "b16": (jnp.arange(5, dtype=jnp.float32) / k
                    ).astype(jnp.bfloat16)}

def like(tree, fill):
    return jax.tree_util.tree_map(
        lambda x: jnp.full(x.shape, fill, x.dtype), tree)

def same_bits(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(
        x.dtype == y.dtype and x.shape == y.shape
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))

def reference(tx, grads_of, params, calls, prefix, compression=Compression.none,
              bpps=1, overlap=False):
    # The tree path: enqueue_tree_fused -> wait_tree (one array per leaf)
    # -> a jitted tx.update, with local aggregation as update() does it.
    upd = jax.jit(tx.update)
    flush = jax.jit(lambda a, g: jax.tree_util.tree_map(
        lambda x, y: (x + y) * (1.0 / bpps), a, g))
    combine = jax.jit(lambda *ts: jax.tree_util.tree_map(
        lambda *xs: sum(xs) * (1.0 / bpps), *ts))
    add = jax.jit(lambda a, g: jax.tree_util.tree_map(jnp.add, a, g))
    state, acc, pending, out = tx.init(params), like(params, 0), [], []
    for call in range(calls):
        step, mb = divmod(call, bpps)
        g = grads_of(step, mb)
        if overlap:
            pending.append(wfbp.enqueue_tree_fused(
                g, AVG, compression, 1.0, 1.0, f"{prefix}.mb{mb}"))
        if mb < bpps - 1:
            if not overlap:
                acc = add(acc, g)
            updates = like(g, 0)
        else:
            if overlap:
                g = combine(*[wfbp.wait_tree(p) for p in pending])
                pending = []
            else:
                if bpps > 1:
                    g, acc = flush(acc, g), like(params, 0)
                g = wfbp.wait_tree(wfbp.enqueue_tree_fused(
                    g, AVG, compression, 1.0, 1.0, prefix))
            updates, state = upd(g, state, params)
        out.append((updates, state))
        params = optax.apply_updates(params, updates)
    return out

def buffer_path(tx, grads_of, params, calls, **kw):
    dopt = hvd.DistributedOptimizer(tx, **kw)
    state, out = dopt.init(params), []
    before = phase_stats.snapshot().get("tree_unflatten", {"count": 0})
    for call in range(calls):
        step, mb = divmod(call, kw.get("backward_passes_per_step", 1))
        updates, state = dopt.update(grads_of(step, mb), state, params)
        out.append((updates, state.inner_state))
        params = optax.apply_updates(params, updates)
    after = phase_stats.snapshot().get("tree_unflatten", {"count": 0})
    return out, after["count"] - before["count"]

CASES = {
    "sgd_momentum": dict(tx=optax.sgd(0.1, momentum=0.9), grads=f32_tree),
    "adamw": dict(tx=optax.adamw(1e-3, weight_decay=0.01), grads=f32_tree),
    "mixed_dtypes": dict(tx=optax.sgd(0.1, momentum=0.9), grads=mixed_tree),
    "fp16": dict(tx=optax.sgd(0.1, momentum=0.9), grads=f32_tree,
                 kw=dict(compression=Compression.fp16)),
    "bpps2": dict(tx=optax.sgd(0.1, momentum=0.9), grads=f32_tree, bpps=2),
    "overlap2": dict(tx=optax.adamw(1e-3, weight_decay=0.01), grads=f32_tree,
                     bpps=2, overlap=True),
}
report = {}
for name, case in CASES.items():
    bpps, overlap = case.get("bpps", 1), case.get("overlap", False)
    kw = dict(case.get("kw", {}))
    calls = 3 * bpps
    params = like(case["grads"](0), 0.5)
    ref = reference(case["tx"], case["grads"], params, calls, f"ref.{name}",
                    compression=kw.get("compression", Compression.none),
                    bpps=bpps, overlap=overlap)
    if bpps > 1:
        kw.update(backward_passes_per_step=bpps, overlap=overlap)
    got, unflattens = buffer_path(case["tx"], case["grads"], params, calls,
                                  **kw)
    same = [same_bits(g, r) for g, r in zip(got, ref)]
    report[name] = {
        "off": [s for i, s in enumerate(same) if (i + 1) % bpps],
        "flush": [s for i, s in enumerate(same) if not (i + 1) % bpps],
        "tree_unflatten": unflattens,
        "moved": not same_bits(got[-1][0], like(got[-1][0], 0)),
        "groups": len({jnp.asarray(l).dtype.name for l in
                       jax.tree_util.tree_leaves(case["grads"](0))}),
    }

# One instance, two gradient trees: a program each, right on both; ten
# steps on one tree trace tx.update once.
traces = []
inner = optax.sgd(0.1, momentum=0.9)
def counted_update(g, s, p=None):
    traces.append(jax.tree_util.tree_structure(g).num_leaves)
    return inner.update(g, s, p)
counted = optax.GradientTransformation(inner.init, counted_update)
dopt = hvd.DistributedOptimizer(counted)
two = {}
for name, grads_of in (("f32", f32_tree), ("mixed", mixed_tree)):
    params = like(grads_of(0), 0.5)
    ref = reference(inner, grads_of, params, 2, f"ref.two.{name}")
    state, got, p = dopt.init(params), [], params
    for step in range(2):
        updates, state = dopt.update(grads_of(step), state, p)
        got.append((updates, state.inner_state))
        p = optax.apply_updates(p, updates)
    two[name] = [same_bits(g, r) for g, r in zip(got, ref)]
two["traces_after_two_trees"] = list(traces)
params = like(f32_tree(0), 0.5)
state = dopt.init(params)
for step in range(10):
    updates, state = dopt.update(f32_tree(step), state, params)
two["traces_after_ten_more_steps"] = list(traces)
report["two_trees"] = two

# Nothing the caller owns is donated: all of it reads after update returns.
dopt = hvd.DistributedOptimizer(optax.adamw(1e-3, weight_decay=0.01))
params = like(f32_tree(0), 0.5)
grads, state = f32_tree(0), dopt.init(params)
kept = {"grads": grads, "state": state.inner_state, "params": params}
copies = jax.tree_util.tree_map(np.array, kept)
for _ in range(2):
    updates, new_state = dopt.update(grads, state, params)
jax.block_until_ready(updates)
report["caller_owned"] = {
    "deleted": [bool(l.is_deleted()) for l in jax.tree_util.tree_leaves(kept)
                if hasattr(l, "is_deleted")],
    "unchanged": same_bits(jax.tree_util.tree_map(np.array, kept), copies)}
print("REPORT " + json.dumps(report), flush=True)
"""

WORLDS = {
    "np1": (1, {}),
    "np2-host-ring": (2, {}),
    "np2-xla": (2, {"HOROVOD_DATA_PLANE": "xla"}),
}
CASES = ["sgd_momentum", "adamw", "mixed_dtypes", "fp16", "bpps2",
         "overlap2"]


@pytest.fixture(scope="module")
def reports():
    cache = {}

    def get(world):
        if world not in cache:
            n, env = WORLDS[world]
            env = dict(env)
            if env:
                env["HOROVOD_JAX_COORDINATOR"] = f"127.0.0.1:{reserve_port()}"
            outs = run_distributed(n, WORKER, timeout=300, extra_env=env)
            cache[world] = [json.loads(
                [x for x in o.splitlines() if x.startswith("REPORT ")][-1]
                [len("REPORT "):]) for o in outs]
        return cache[world]

    return get


@pytest.mark.timeout(600)
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_buffer_path_is_bit_identical_to_the_tree_path(reports, world, case):
    for rank_report in reports(world):
        r = rank_report[case]
        assert r["flush"] == [True] * 3, r
        assert r["moved"], "the case compared nothing but zeros"
        # The gradient never became one array per leaf.
        assert r["tree_unflatten"] == 0
        assert r["groups"] == (2 if case == "mixed_dtypes" else 1)


@pytest.mark.timeout(600)
@pytest.mark.parametrize("case", ["bpps2", "overlap2"])
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_off_steps_of_local_aggregation_are_bit_identical(reports, world,
                                                          case):
    for rank_report in reports(world):
        assert rank_report[case]["off"] == [True] * 3


@pytest.mark.timeout(600)
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_two_gradient_trees_through_one_instance(reports, world):
    for rank_report in reports(world):
        two = rank_report["two_trees"]
        assert two["f32"] == [True, True] and two["mixed"] == [True, True]
        # One trace of tx.update per (signature, treedef): four leaves,
        # then four leaves of other shapes and dtypes.
        assert two["traces_after_two_trees"] == [4, 4]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_ten_steps_on_one_tree_compile_nothing_more(reports, world):
    for rank_report in reports(world):
        two = rank_report["two_trees"]
        assert two["traces_after_ten_more_steps"] == [4, 4]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_nothing_the_caller_owns_is_donated(reports, world):
    for rank_report in reports(world):
        owned = rank_report["caller_owned"]
        assert owned["deleted"] and not any(owned["deleted"])
        assert owned["unchanged"]


# ---------------------------------------------------------------------------
# the plan and the signature, in this process
# ---------------------------------------------------------------------------


def _leaves():
    import jax.numpy as jnp

    return [jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            jnp.arange(4, dtype=jnp.bfloat16),
            jnp.float32(7.0),
            jnp.arange(3, dtype=jnp.float32)]


def test_leaf_signature_is_the_parents():
    import jax.numpy as jnp

    from horovod_tpu.frameworks.jax.wfbp import _leaf_signature

    leaves = [jnp.ones((2, 3), jnp.float32), jnp.ones((5,), jnp.bfloat16),
              np.ones((4,), np.float64), np.ones((2, 2), np.float32),
              np.int64(3), 1.5, 2, jnp.int32(4)]
    # What ``(tuple(l.shape), jnp.asarray(l).dtype.name)`` gave per leaf:
    # NumPy float64 and int64 canonicalise to 32 bits, Python scalars to
    # JAX's defaults.
    assert _leaf_signature(leaves) == (
        ((2, 3), "float32"), ((5,), "bfloat16"), ((4,), "float32"),
        ((2, 2), "float32"), ((), "int32"), ((), "float32"), ((), "int32"),
        ((), "int32"))


def test_leaf_signature_asks_a_jax_array_for_its_own_dtype(monkeypatch):
    import jax.numpy as jnp

    from horovod_tpu.frameworks.jax import wfbp

    leaves = _leaves()
    monkeypatch.setattr(jnp, "asarray", lambda *a, **k: pytest.fail(
        "jnp.asarray called for a jax.Array leaf"))
    assert wfbp._leaf_signature(leaves) == (
        ((2, 3), "float32"), ((4,), "bfloat16"), ((), "float32"),
        ((3,), "float32"))


def test_plan_groups_by_dtype_in_first_seen_order():
    from horovod_tpu.frameworks.jax.wfbp import _fuse_plan, _leaf_signature

    sig = _leaf_signature(_leaves())
    plan = _fuse_plan(sig)
    assert plan.sig == sig
    assert plan.groups == [("float32", [0, 2, 3]), ("bfloat16", [1])]
    assert _fuse_plan(sig) is plan                  # one per signature


@pytest.mark.parametrize("jitted", [False, True])
def test_unflatten_inverts_flatten_from_the_signature_alone(jitted):
    from horovod_tpu.frameworks.jax.wfbp import _fuse_plan, _leaf_signature

    leaves = _leaves()
    plan = _fuse_plan(_leaf_signature(leaves))
    bufs = plan.flatten(leaves)
    assert [b.shape for b in bufs] == [(10,), (4,)]
    out = (plan.unflatten_jit if jitted else plan.unflatten)(bufs)
    assert len(out) == len(leaves)
    for got, want in zip(out, leaves):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_unflatten_traces_inside_a_consumers_program():
    import jax

    from horovod_tpu.frameworks.jax.wfbp import _fuse_plan, _leaf_signature

    leaves = _leaves()
    plan = _fuse_plan(_leaf_signature(leaves))

    @jax.jit
    def consumer(bufs):
        return sum(x.astype("float32").sum() for x in plan.unflatten(bufs))

    want = sum(float(np.asarray(x, np.float32).sum()) for x in leaves)
    assert float(consumer(plan.flatten(leaves))) == want
    # One scalar out: the cut made no output buffer of its own.
    assert len(jax.tree_util.tree_leaves(
        jax.eval_shape(consumer, plan.flatten(leaves)))) == 1


def test_pending_tree_holds_no_gradient_array():
    from horovod_tpu.frameworks.jax.wfbp import PendingTree

    assert PendingTree._fields == ("handles", "ctxs", "plan", "treedef",
                                   "compression")


def test_runtime_down_update_is_the_plain_inner_update():
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.frameworks.jax import ops
    from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer

    if ops.initialized():
        pytest.skip("a runtime is up in this process")
    tx = optax.adamw(1e-3, weight_decay=0.01)
    params = {"w": jnp.ones((2, 3)), "b": jnp.zeros((3,))}
    grads = {"w": jnp.full((2, 3), 0.25), "b": jnp.full((3,), -0.5)}
    dopt = DistributedOptimizer(tx)
    updates, state = dopt.update(grads, dopt.init(params), params)
    want, want_state = jax.jit(tx.update)(grads, tx.init(params), params)
    for got, ref in ((updates, want), (state.inner_state, want_state)):
        for x, y in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
