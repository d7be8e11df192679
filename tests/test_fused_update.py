"""``DistributedOptimizer.update`` feeds the reduced gradient to the
optimizer's program as its fused per-dtype buffers (ISSUE 26), and keeps
the inner optimizer's state between steps in the same form, a
``wfbp.FusedTree`` (ISSUE 30).

The tree path stays in the tree as the reference: ``wfbp.wait_tree`` (one
array per leaf) and a jitted ``tx.update`` on that tree, composed in the
worker exactly as ``update`` composed them before.  Every case holds the
buffer path to the same bits, step after step, on ``(updates,
state.inner_state.unfuse())``.  One worker per world runs every case and
prints a report; the cases below each assert one fact of it.
"""

import json

import numpy as np
import pytest

from .helpers import reserve_port, run_distributed

# What both workers start with.
HELPERS = """
import json, tempfile
import jax, jax.numpy as jnp, optax
from horovod_tpu.core.timeline import phase_stats
from horovod_tpu.frameworks.jax import checkpoint, wfbp
from horovod_tpu.frameworks.jax.compression import Compression

def same_bits(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(
        x.dtype == y.dtype and x.shape == y.shape
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))

def count(name):
    return phase_stats.snapshot().get(name, {"count": 0})["count"]
"""

WORKER = HELPERS + """

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

def buffer_path(tx, grads_of, params, calls, tree_in=False, **kw):
    dopt = hvd.DistributedOptimizer(tx, **kw)
    state, out, forms = dopt.init(params), [], []
    if tree_in:
        # As a state built by hand or restored from an older checkpoint.
        state = state._replace(inner_state=state.inner_state.unfuse())
    unflattens, joins = count("tree_unflatten"), []
    for call in range(calls):
        step, mb = divmod(call, kw.get("backward_passes_per_step", 1))
        before = count("state_fuse")
        updates, state = dopt.update(grads_of(step, mb), state, params)
        joins.append(count("state_fuse") - before)
        forms.append(type(state.inner_state).__name__)
        out.append((updates, state.inner_state.unfuse()
                    if forms[-1] == "FusedTree" else state.inner_state))
        params = optax.apply_updates(params, updates)
    return out, {"tree_unflatten": count("tree_unflatten") - unflattens,
                 "state_fuse": joins, "forms": forms,
                 "arrays": len(jax.tree_util.tree_leaves(state.inner_state)),
                 "state": state, "dopt": dopt, "params": params}

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
    got, facts = buffer_path(case["tx"], case["grads"], params, calls, **kw)
    same = [same_bits(g, r) for g, r in zip(got, ref)]
    state_leaves = jax.tree_util.tree_leaves(ref[-1][1])
    report[name] = {
        "off": [s for i, s in enumerate(same) if (i + 1) % bpps],
        "flush": [s for i, s in enumerate(same) if not (i + 1) % bpps],
        "tree_unflatten": facts["tree_unflatten"],
        "state_fuse": facts["state_fuse"],
        "forms": facts["forms"],
        "state_arrays": facts["arrays"],
        "state_groups": len({l.dtype.name for l in state_leaves}),
        "state_leaves": len(state_leaves),
        "moved": not same_bits(got[-1][0], like(got[-1][0], 0)),
        "groups": len({jnp.asarray(l).dtype.name for l in
                       jax.tree_util.tree_leaves(case["grads"](0))}),
    }

# A state that arrives as a plain tree goes in as it is and comes out as
# buffers: one call joins, the rest find buffers.
tree_in = {}
for name in ("sgd_momentum", "adamw", "mixed_dtypes"):
    case = CASES[name]
    params = like(case["grads"](0), 0.5)
    ref = reference(case["tx"], case["grads"], params, 3, f"ref.in.{name}")
    got, facts = buffer_path(case["tx"], case["grads"], params, 3,
                             tree_in=True)
    tree_in[name] = {"same": [same_bits(g, r) for g, r in zip(got, ref)],
                     "state_fuse": facts["state_fuse"],
                     "forms": facts["forms"]}
report["tree_in"] = tree_in

# A leaf above the size limit stays an array of its own, beside the
# buffers of the rest.  The limit is lowered to 64 bytes here, between
# this tree's leaves (w1 128 B, b1 32 B, w2 64 B, b2 8 B); the test of
# _split below holds the real one to real sizes.
limit, wfbp._JOIN_LIMIT_BYTES = wfbp._JOIN_LIMIT_BYTES, 64
whole = {}
for name in ("sgd_momentum", "adamw"):
    case = CASES[name]
    params = like(case["grads"](0), 0.5)
    ref = reference(case["tx"], case["grads"], params, 3, f"ref.big.{name}")
    got, facts = buffer_path(case["tx"], case["grads"], params, 3)
    inner = facts["state"].inner_state
    whole[name] = {"same": [same_bits(g, r) for g, r in zip(got, ref)],
                   "state_fuse": facts["state_fuse"],
                   "shapes": [list(np.shape(b)) for b in inner.buffers]}
wfbp._JOIN_LIMIT_BYTES = limit
report["whole_leaves"] = whole

# What hvd_optimizer_update returns: a step's output arrays less those of
# the programs around it (the flatten and, on the XLA plane, the
# allreduce), which the same enqueue and wait alone count.
outputs = {}
for name in ("sgd_momentum", "adamw", "mixed_dtypes"):
    case = CASES[name]
    params = like(case["grads"](0), 0.5)
    _, facts = buffer_path(case["tx"], case["grads"], params, 2)
    g = case["grads"](2)
    before = count("program_call")
    wfbp.wait_buffers(wfbp.enqueue_tree_fused(
        g, AVG, Compression.none, 1.0, 1.0, f"count.{name}"))
    around = count("program_call") - before
    before = count("program_call")
    facts["dopt"].update(g, facts["state"], facts["params"])
    outputs[name] = count("program_call") - before - around
report["update_outputs"] = outputs

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
        got.append((updates, state.inner_state.unfuse()))
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
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_state_lives_as_one_array_per_dtype(reports, world, case):
    for rank_report in reports(world):
        r = rank_report[case]
        assert set(r["forms"]) == {"FusedTree"}
        # AdamW: float moments and an integer count.
        assert r["state_groups"] == {"adamw": 2, "overlap2": 2,
                                     "mixed_dtypes": 2}.get(case, 1)
        assert r["state_arrays"] == r["state_groups"] < r["state_leaves"]
        # init returned buffers, so no update had a tree to join.
        assert r["state_fuse"] == [0] * len(r["forms"])


@pytest.mark.timeout(600)
@pytest.mark.parametrize("case", ["sgd_momentum", "adamw", "mixed_dtypes"])
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_a_tree_state_goes_in_and_buffers_come_out(reports, world, case):
    for rank_report in reports(world):
        r = rank_report["tree_in"][case]
        assert r["same"] == [True] * 3
        assert r["forms"] == ["FusedTree"] * 3
        assert r["state_fuse"] == [1, 0, 0]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("case", ["sgd_momentum", "adamw"])
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_a_leaf_above_the_limit_stays_its_own_array(reports, world, case):
    for rank_report in reports(world):
        r = rank_report["whole_leaves"][case]
        assert r["same"] == [True] * 3 and r["state_fuse"] == [0] * 3
        # b1, w2 and b2 (8 + 16 + 2 elements) joined, w1 (4 x 8) whole;
        # AdamW has two such trees and its count in a group of its own.
        assert r["shapes"] == {
            "sgd_momentum": [[26], [4, 8]],
            "adamw": [[1], [52], [4, 8], [4, 8]]}[case]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("case", ["sgd_momentum", "adamw", "mixed_dtypes"])
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_update_program_returns_leaves_plus_groups(reports, world, case):
    leaves = 4
    groups = {"sgd_momentum": 1, "adamw": 2, "mixed_dtypes": 2}[case]
    state_leaves = {"sgd_momentum": 4, "adamw": 9, "mixed_dtypes": 4}[case]
    for rank_report in reports(world):
        got = rank_report["update_outputs"][case]
        assert got == leaves + groups < leaves + state_leaves


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
# a fused state through JaxState and through a checkpoint, at np = 2
# ---------------------------------------------------------------------------

RESUME_WORKER = HELPERS + """
def grads_of(step):
    k = float(1 + rank + 3 * step)
    return {"w": jnp.linspace(-1.0, 1.0, 40).reshape(5, 8) * k,
            "b": jnp.arange(8, dtype=jnp.float32) / (3.0 * k),
            "s": jnp.full((3,), 0.37 * k)}

tx = optax.adamw(1e-2, weight_decay=0.01)
params0 = jax.tree_util.tree_map(lambda x: jnp.full(x.shape, 0.5), grads_of(0))

def run(dopt, params, state, steps):
    out = []
    for step in steps:
        updates, state = dopt.update(grads_of(step), state, params)
        params = optax.apply_updates(params, updates)
        out.append((updates, state.inner_state.unfuse()))
    return params, state, out

# The uninterrupted run.
straight = hvd.DistributedOptimizer(tx, name="straight")
_, _, want = run(straight, params0, straight.init(params0), range(4))

# JaxState: rank 0 commits after step 1 and rank 1 after step 0; a step
# later both roll back, and the sync makes rank 0's commit everyone's.
dopt = hvd.DistributedOptimizer(tx, name="elastic")
params, opt_state, _ = run(dopt, params0, dopt.init(params0), [0])
state = hvd.elastic.JaxState(params=params, opt_state=opt_state)
params, opt_state, _ = run(dopt, params, opt_state, [1])
if rank == 0:
    state.params, state.opt_state = params, opt_state
    state.save()
run(dopt, params, opt_state, [2])                    # the step that is lost
state.restore()
saved = state.opt_state.inner_state
assert isinstance(saved, wfbp.FusedTree), type(saved)
assert all(isinstance(b, np.ndarray) for b in saved.buffers), saved.buffers
state.sync()
assert isinstance(state.opt_state.inner_state, wfbp.FusedTree)
joins = count("state_fuse")
_, _, got = run(dopt, state.params, state.opt_state, [2, 3])
print("ELASTIC_SAME", rank, [same_bits(g, w) for g, w in zip(got, want[2:])],
      count("state_fuse") - joins, flush=True)

# A checkpoint of the fused state, and one written before the state had
# this form (the plain optax tree), both restored like the live state.
dopt = hvd.DistributedOptimizer(tx, name="ckpt")
params, opt_state, _ = run(dopt, params0, dopt.init(params0), [0, 1])
base = tempfile.mkdtemp() if rank == 0 else "/nonexistent"
live = {"params": params, "opt": opt_state}
checkpoint.save(base + "/new", live)
restored = checkpoint.restore(base + "/new", like=live)
assert isinstance(restored["opt"].inner_state, wfbp.FusedTree)
assert same_bits(restored["opt"].inner_state.unfuse(),
                 opt_state.inner_state.unfuse())
_, _, got = run(dopt, restored["params"], restored["opt"], [2, 3])
print("CKPT_SAME", rank, [same_bits(g, w) for g, w in zip(got, want[2:])],
      flush=True)

old = {"params": params,
       "opt": opt_state._replace(inner_state=opt_state.inner_state.unfuse())}
checkpoint.save(base + "/old", old)
restored = checkpoint.restore(base + "/old", like=old)
joins = count("state_fuse")
_, state_after, got = run(dopt, restored["params"], restored["opt"], [2, 3])
print("OLD_CKPT_SAME", rank,
      [same_bits(g, w) for g, w in zip(got, want[2:])],
      count("state_fuse") - joins,
      type(state_after.inner_state).__name__, flush=True)
"""


@pytest.fixture(scope="module")
def resumed():
    outs = run_distributed(2, RESUME_WORKER, timeout=300)
    return [{line.split()[0]: line for line in o.splitlines()
             if "_SAME " in line} for o in outs]


@pytest.mark.timeout(600)
def test_jax_state_saves_restores_and_syncs_a_fused_state(resumed):
    for r, lines in enumerate(resumed):
        # No update after the restore had a tree to join.
        assert lines["ELASTIC_SAME"] == f"ELASTIC_SAME {r} [True, True] 0"


@pytest.mark.timeout(600)
@pytest.mark.parametrize("line", [
    pytest.param("CKPT_SAME {r} [True, True]", id="fused"),
    pytest.param("OLD_CKPT_SAME {r} [True, True] 1 FusedTree", id="tree")])
def test_checkpoint_round_trips_the_state(resumed, line):
    for r, lines in enumerate(resumed):
        assert lines[line.split()[0]] == line.format(r=r)


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


def _no_runtime():
    """The cases below read the update where no runtime is up.  A file that
    ran before this one in the same process may have left one up (the cells'
    suites call ``hvd.init()`` and leave it), and which files those are is
    the order's to say: shut it down and make the state new, so that the
    case runs whatever ran before and a later ``init()`` starts afresh."""
    from horovod_tpu.core import state
    from horovod_tpu.frameworks.jax import ops

    if ops.initialized():
        state.global_state().shutdown()
        state.reset_global_state()
    assert not ops.initialized()


def _same_bits(got, want):
    import jax

    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


@pytest.mark.parametrize("tree_in", [False, True])
@pytest.mark.parametrize("tx_name", ["sgd_momentum", "adamw"])
def test_runtime_down_update_is_the_plain_inner_update(tx_name, tree_in):
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.core.timeline import phase_stats
    from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer
    from horovod_tpu.frameworks.jax.wfbp import FusedTree

    _no_runtime()
    tx = {"sgd_momentum": optax.sgd(0.1, momentum=0.9),
          "adamw": optax.adamw(1e-3, weight_decay=0.01)}[tx_name]
    params = {"w": jnp.ones((2, 3)), "b": jnp.zeros((3,))}
    grads = {"w": jnp.full((2, 3), 0.25), "b": jnp.full((3,), -0.5)}
    dopt = DistributedOptimizer(tx)
    state = dopt.init(params)
    assert isinstance(state.inner_state, FusedTree)
    _same_bits(state.inner_state.unfuse(), tx.init(params))
    if tree_in:
        state = state._replace(inner_state=tx.init(params))
    want_state, reference = tx.init(params), jax.jit(tx.update)
    joins = []
    for _ in range(3):
        before = phase_stats.snapshot().get("state_fuse", {"count": 0})
        updates, state = dopt.update(grads, state, params)
        joins.append(phase_stats.snapshot().get(
            "state_fuse", {"count": 0})["count"] - before["count"])
        want, want_state = reference(grads, want_state, params)
        # The state has one form whether or not the runtime is up.
        assert isinstance(state.inner_state, FusedTree)
        _same_bits(updates, want)
        _same_bits(state.inner_state.unfuse(), want_state)
    assert joins == [int(tree_in), 0, 0]


@pytest.mark.parametrize("loop", ["jit", "scan"])
@pytest.mark.parametrize("form", ["tree", "fused"])
@pytest.mark.parametrize("tx_name", ["sgd_momentum", "adamw"])
def test_inside_a_callers_jit_the_state_keeps_its_form(tx_name, form, loop):
    """``jax.jit(tx.init)`` and ``jax.jit(tx.update)`` are this repo's own
    idiom (``models/training.py``): a traced leaf has no device to ask,
    and the caller's program owns its outputs, so a tree stays a tree
    (also as the carry of a ``scan``) and a fused state stays fused."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.core.timeline import phase_stats
    from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer
    from horovod_tpu.frameworks.jax.wfbp import FusedTree, single_device

    _no_runtime()
    tx = {"sgd_momentum": optax.sgd(0.1, momentum=0.9),
          "adamw": optax.adamw(1e-3, weight_decay=0.01)}[tx_name]
    params = {"w": jnp.ones((2, 3)), "b": jnp.zeros((3,))}
    grads = {"w": jnp.full((2, 3), 0.25), "b": jnp.full((3,), -0.5)}
    seen = []
    jax.jit(lambda p: seen.append(single_device(p)) or p)(params)
    assert single_device(params) and seen == [False]
    dopt = DistributedOptimizer(tx)
    joins = phase_stats.snapshot().get("state_fuse", {"count": 0})["count"]
    if form == "tree":
        state = jax.jit(dopt.init)(params)
        _same_bits(state.inner_state, tx.init(params))
    else:
        state = dopt.init(params)
    kind = FusedTree if form == "fused" else tuple
    assert type(state.inner_state) is kind

    def three_steps(opt, state):
        # The caller's program, with ``opt`` the wrapper or optax's own.
        if loop == "jit":
            step, updates = jax.jit(opt.update), []
            for _ in range(3):
                u, state = step(grads, state, params)
                updates.append(u)
            return updates, state

        def body(carry, _):
            u, carry = opt.update(grads, carry, params)
            return carry, u
        state, updates = jax.jit(
            lambda s: jax.lax.scan(body, s, None, length=3))(state)
        return updates, state

    got, state = three_steps(dopt, state)
    want, want_state = three_steps(tx, tx.init(params))
    inner = state.inner_state
    assert type(inner) is kind
    _same_bits(got, want)
    _same_bits(inner.unfuse() if form == "fused" else inner, want_state)
    # No traced call counts as an update that had to join a tree.
    assert phase_stats.snapshot().get(
        "state_fuse", {"count": 0})["count"] == joins


def test_split_holds_real_sizes_to_the_limit():
    from horovod_tpu.frameworks.jax.wfbp import _JOIN_LIMIT_BYTES, _split

    # ResNet-50's largest leaf (3 x 3 x 512 x 512 float32, 9.4 MB) joins;
    # BERT-large's FFN matrix (16.8 MB) and embedding (125 MB) do not.
    assert 9_437_184 < _JOIN_LIMIT_BYTES < 16_777_216
    sig = (((3, 3, 512, 512), "float32"), ((30522, 1024), "float32"),
           ((), "int32"), ((4096, 1024), "float32"),
           ((4096, 2048), "bfloat16"), ((4096,), "float32"))
    assert _split(sig) == ((0, 2, 5), (1, 3, 4))


def test_fused_tree_is_a_pytree_of_its_buffers():
    import copy
    import pickle

    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.frameworks.jax.wfbp import FusedTree

    tree = optax.adamw(1e-3).init({"w": jnp.ones((2, 3)), "b": jnp.ones(3)})
    fused = FusedTree.fuse(tree)
    assert [b.shape for b in jax.tree_util.tree_leaves(fused)] == [(1,), (18,)]
    _same_bits(fused.unfuse(), tree)
    # Through tree_map (JaxState's snapshot), a deep copy (its restore), a
    # pickle (broadcast_object) and jit, and back.
    host = jax.tree_util.tree_map(np.asarray, fused)
    assert all(isinstance(b, np.ndarray) for b in host.buffers)
    for other in (host, copy.deepcopy(host), pickle.loads(pickle.dumps(host)),
                  jax.jit(lambda f: f)(fused)):
        assert isinstance(other, FusedTree)
        assert jax.tree_util.tree_structure(other) == \
            jax.tree_util.tree_structure(fused)
        _same_bits(other.unfuse(), tree)
    # Cut inside a consumer's program, the leaves are no outputs.
    total = jax.jit(lambda f: sum(x.astype("float32").sum()
                                  for x in f.leaves()))(fused)
    assert float(total) == 0.0


def test_a_sharded_state_stays_a_tree():
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.frameworks.jax import wfbp
    from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer

    _no_runtime()
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    params = {"w": jax.device_put(jnp.ones((4, 3)),
                                  NamedSharding(mesh, P("x"))),
              "b": jnp.zeros((3,))}
    assert not wfbp.single_device(params) and wfbp.single_device(params["b"])
    tx = optax.sgd(0.1, momentum=0.9)
    dopt = DistributedOptimizer(tx)
    state = dopt.init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    want_state = tx.init(params)
    for _ in range(2):
        updates, state = dopt.update(grads, state, params)
        want, want_state = jax.jit(tx.update)(grads, want_state, params)
        assert not isinstance(state.inner_state, wfbp.FusedTree)
        _same_bits(updates, want)
        _same_bits(state.inner_state, want_state)
    assert state.inner_state[0].trace["w"].sharding.spec == P("x")
