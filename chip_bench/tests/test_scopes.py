"""``chip_bench/scopes.py``: the rule that names an operation's block, on the
forms JAX writes, and the raw reader on the two recorded traces against the
hand values of ``data/README.txt``.  The traces date from before the scopes
(PR 23), so every operation in them is ``unscoped`` or a ``collective``; what
they prove is the read: ``tf_op``, ``hlo_category``, XLA's counts, the clock.

``tests/test_device_scopes.py`` imports these cases, so tier-1 runs them too.
"""

import os

import pytest

from chip_bench import scopes
from chip_bench.tools import step_account
from chip_bench.trace_reduce import Trace, Window

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NP1 = os.path.join(DATA, "small_np1.xplane.pb")
NP4 = os.path.join(DATA, "small_np4.xplane.pb")
NS = 1e-9


@pytest.mark.parametrize("tf_op,block,direction", [
    # forward and backward as JAX 0.9.0 writes them, flax's names around
    ("jit(step)/jvp(M)/layer_1/hvd.ffn/ffn_in/dot_general", "ffn", "fwd"),
    ("jit(step)/transpose(jvp(M))/layer_1/hvd.ffn/ffn_in/dot_general",
     "ffn", "bwd"),
    # the step builder's scope outermost, inside the transforms' brackets
    ("jit(_step)/jvp(hvd.loss)/reduce_sum", "loss", "fwd"),
    ("jit(_step)/transpose(jvp(hvd.loss))/Transformer/hvd.norm/ln_f/mul",
     "norm", "bwd"),
    # nested: the innermost names the row
    ("jit(_step)/jvp(hvd.loss)/M/layer_0/attn/hvd.attn.layout/transpose",
     "attn.layout", "fwd"),
    ("jit(_step)/jvp(hvd.loss)/hvd.resnet.stage2/B_0/BatchNorm_1/hvd.bn/"
     "BatchNorm_1/reduce_sum", "bn", "fwd"),
    ("jit(_step)/transpose(jvp(hvd.loss))/hvd.moe.combine/while/body/"
     "hvd.moe.dispatch/gather", "moe.dispatch", "bwd"),
    # no direction under these, whatever the path says
    ("jit(_step)/hvd.optimizer/transpose(x)/mul", "optimizer", ""),
    ("jit(hvd_optimizer_update)/hvd.optimizer/hvd.fuse/dynamic_update_slice",
     "fuse", ""),
    ("jit(hvd_local_allreduce)/hvd.allreduce/convert_element_type",
     "allreduce", ""),
    # none: the profiler's trailing colon, a bare name, nothing at all
    ("jit(grad_step)/dot_general:", "unscoped", "fwd"),
    ("jit(loss)/transpose(jvp())/mul", "unscoped", "bwd"),
    ("", "unscoped", "fwd"),
    # a jitted program's or a kernel's name is no scope
    ("jit(hvd_tree_flatten)/concatenate", "unscoped", "fwd"),
    ("jit(s)/jvp(hvd.loss)/hvd.moe.combine/hvd_rows_to_tokens",
     "moe.combine", "fwd"),
    # names XLA merged: the first that carries a scope
    ("jit(s)/mul;jit(s)/jvp(hvd.loss)/hvd.head/dot_general", "head", "fwd"),
])
def test_block_and_direction_on_the_forms_jax_writes(tf_op, block, direction):
    assert scopes.block(tf_op) == block
    assert scopes.direction(tf_op) == direction


def test_segments_are_listed_outermost_first():
    assert scopes.segments(
        "jit(s)/jvp(hvd.loss)/hvd.resnet.stem/bn_init/hvd.bn/div") == \
        ["loss", "resnet.stem", "bn"]
    assert scopes.segments("jit(f)/reduce_sum:") == []


@pytest.mark.parametrize("path", [NP1, NP4], ids=["np1", "np4"])
def test_the_raw_read_is_trace_reduces_op_line(path):
    """Same operations, same names, the same seconds to the bit: a scope's
    time is cut from the stretch ``Window`` cuts."""
    ops = scopes.device_ops(path)
    assert [(op.name, op.start, op.end) for op in ops] == \
        Trace.from_file(path, ()).ops


def _at(path, name, start_ns):
    found = [op for op in scopes.device_ops(path)
             if op.name == name and round(op.start / NS) == start_ns]
    assert len(found) == 1, found
    return found[0]


def test_a_matmul_fusion_of_np1_by_hand():
    # README.txt: fusion.3 of step 3 starts at 51213233 and takes 89970 ns.
    op = _at(NP1, "fusion.3", 51213233)
    assert round((op.end - op.start) / NS) == 89970
    assert op.tf_op == "jit(grad_step)/dot_general:"
    assert op.hlo_category == "convolution fusion"
    # 2 x 2048^3 + 2048^2 x 4: XLA's count of the product and its convert.
    assert op.flops == 17196646400
    assert op.bytes_accessed == 3 * 2048 * 2048 * 2
    assert scopes.row_of(op) == ("unscoped", "fwd")


def test_the_allreduce_of_np4_by_hand():
    # README.txt: all-reduce of step 3 starts at 67245259, 283667 ns.
    op = _at(NP4, "all-reduce", 67245259)
    assert round((op.end - op.start) / NS) == 283667
    assert op.tf_op == "jit(f)/reduce_sum:"
    assert op.hlo_category == "all-reduce"
    assert scopes.row_of(op) == ("collective", "")


def _window(path):
    return Window.between_reads(Trace.from_file(path, ("loss_read",)))


@pytest.mark.parametrize("path", [NP1, NP4], ids=["np1", "np4"])
def test_the_rows_add_up_to_the_op_line(path):
    w = _window(path)
    rows = scopes.account(scopes.device_ops(path), w.lo, w.hi)
    assert sum(r.seconds for r in rows.values()) == pytest.approx(
        sum(e - s for _, s, e in w.ops), rel=1e-12)
    assert sum(r.ops for r in rows.values()) == w.op_count("")


def test_the_account_of_np4_by_hand():
    w = _window(NP4)
    rows = scopes.account(scopes.device_ops(NP4), w.lo, w.hi)
    assert set(rows) == {("collective", ""), ("unscoped", "fwd")}
    # README.txt: the three all-reduces of the stretch.
    collective = rows["collective", ""]
    assert collective.seconds == pytest.approx(
        (283667 + 283065 + 282890) * NS, rel=1e-9)
    assert collective.ops == 3
    assert collective.by_prefix == {"jit(f)": pytest.approx(
        collective.seconds)}
    # Steps 4 and 5 hold their matmul programs whole, step 3's ended before
    # the stretch: 2 x (3 products + the product with the loss's sum).
    assert rows["unscoped", "fwd"].flops == pytest.approx(
        2 * (3 * 17196646400 + 17205035008)
        + 3 * (4194304 + 8388608), rel=1e-12)


def test_the_reductions_read_what_the_harness_hands_them():
    w = _window(NP4)
    ctx = {"window": w, "xplane": NP4}
    per_step = scopes.REDUCTIONS["trace_scope_ms_per_step"]
    assert per_step({"scope": "collective"}, ctx) == pytest.approx(
        (283667 + 283065 + 282890) * 1e-6 / 3, rel=1e-9)
    assert per_step({"scope": "unscoped", "direction": "fwd",
                     "pattern": "^all-reduce|^fusion"}, ctx) == \
        pytest.approx(1e3 * w.op_s(r"^fusion") / 3, rel=1e-9)
    assert per_step({"scope": "unscoped", "direction": "bwd"}, ctx) is None
    assert per_step({"scope": "ffn"}, ctx) is None
    # No operation of this trace carries a scope: nothing to read, not 100%.
    assert scopes.REDUCTIONS["trace_unscoped_pct"]({}, ctx) is None


@pytest.mark.parametrize("ctx", [
    {"window": None, "xplane": NP4}, {"window": None}, {}],
    ids=["no_window", "no_trace", "parent_harness"])
def test_the_reductions_find_nothing_where_there_is_nothing(ctx):
    """Today's ``worker.py`` hands on no ``xplane``: None, and no raise."""
    for reduce in scopes.REDUCTIONS.values():
        assert reduce({"scope": "ffn"}, ctx) is None
    with_window = dict(ctx, window=_window(NP4))
    if "xplane" not in ctx:
        for reduce in scopes.REDUCTIONS.values():
            assert reduce({"scope": "collective"}, with_window) is None


def test_the_tool_prints_an_account_that_adds_up(capsys):
    assert step_account.main([NP4, "--ops", "2"]) == 0
    out = capsys.readouterr().out
    assert "collective" in out and "all-reduce" in out
    assert "jit(grad_step)" in out        # unscoped's largest prefix
    assert out.rstrip().endswith("equal") and "NOT EQUAL" not in out


def _module(instructions, computations=()):
    """An ``HloModule`` of one entry computation of ``instructions``
    [(id, name, op_name, operands, called)] and further ``computations``
    [(id, root id, instructions)]."""
    proto = scopes._messages()["HloProto"]()
    for ident, root, body in ((1, 0, instructions), *computations):
        computation = proto.hlo_module.computations.add(id=ident, root_id=root)
        for i, name, op_name, operands, called in body:
            ins = computation.instructions.add(id=i, name=name,
                                               operand_ids=operands,
                                               called_computation_ids=called)
            ins.metadata.op_name = op_name
    return scopes.Program(proto.hlo_module)


def test_an_instruction_without_a_name_is_adopted_by_the_hlo_around_it():
    ffn, norm = "jit(s)/jvp(hvd.loss)/hvd.ffn/dot", \
        "jit(s)/transpose(jvp(hvd.loss))/hvd.norm/mul"
    program = _module(
        [(10, "p0", "p['w']", [], []),
         (11, "copy.1", "", [10], []),             # its user names it
         (12, "fusion.1", ffn, [11], []),
         (13, "fusion.2", "", [12], [2]),          # what it calls names it
         (14, "copy-start.1", "", [13], []),       # two hops to a user
         (15, "copy-done.1", "", [14], []),
         (16, "fusion.3", norm, [15], []),
         (17, "fusion.4", "", [16], [3]),          # the commonest inside
         (18, "copy.2", "jit(s)/reshape", [], []),  # nothing near
         (19, "tuple.1", "", [18], [])],
        [(2, 21, [(20, "param_0", "", [], []), (21, "add.1", norm, [20], [])]),
         (3, 32, [(30, "mul.1", ffn, [], []), (31, "mul.2", ffn, [], []),
                  (32, "tuple.2", "", [30, 31], [])])])
    assert program.adopted("copy.1") == ffn
    assert program.adopted("fusion.2") == norm
    assert program.adopted("copy-start.1") == norm
    assert program.adopted("copy-done.1") == norm
    assert program.adopted("fusion.4") == ffn
    assert program.adopted("copy.2") == ""
    assert program.adopted("no such instruction") == ""
    # What XLA fused into one operation, by block.
    assert program.inside("fusion.2") == ("norm",)
    assert program.inside("fusion.4") == ("ffn",)
    assert program.inside("copy.1") == ()


@pytest.mark.parametrize("name,tf_op,adopted,row", [
    # its own scope wins over whatever is around
    ("fusion.1", "jit(s)/hvd.optimizer/mul", "jit(s)/hvd.norm/add",
     ("optimizer", "")),
    ("copy.3", "", "jit(s)/transpose(jvp(hvd.loss))/hvd.norm/mul",
     ("norm", "bwd")),
    ("copy.4", "p['layer_0']['q']['kernel']:", "jit(s)/jvp(hvd.loss)/"
     "hvd.attn.proj/dot_general", ("attn.proj", "fwd")),
    # XLA's expansion of lax.ragged_dot: the block by name, no direction
    ("ragged-dot-none.7", "ragged-dot-none:",
     "jit(s)/transpose(jvp(hvd.loss))/hvd.moe.combine/mul",
     ("moe.experts", "")),
    ("ragged-dot-none.8", "ragged-dot-none:", "", ("moe.experts", "")),
    ("copy-done.9", "", "", ("unscoped", "fwd")),
])
def test_the_row_of_an_operation_xla_made(name, tf_op, adopted, row):
    op = scopes.Op(name, 0.0, 1.0, tf_op, "", 0, 0, adopted)
    assert scopes.row_of(op) == row
    assert scopes.is_adopted(op) == (not scopes.segments(tf_op)
                                     and row[0] != "unscoped")
    taken = scopes.account([op._replace(also=("bn", "norm"))], 0.0, 1.0)[row]
    assert taken.adopted_s == (1.0 if scopes.is_adopted(op) else 0.0)
    assert taken.also == {"bn": 1.0, "norm": 1.0}


def test_the_recorded_traces_hold_their_programs():
    """``/host:metadata`` of np4: four modules; the 8 MB ``copy`` before the
    allreduce has no name and its user's (``jit(f)/reshape``) no scope."""
    ops = scopes.device_ops(NP4)
    assert {op.adopted for op in ops} == {""}
    space = scopes._messages()["XSpace"]()
    with open(NP4, "rb") as f:
        space.ParseFromString(f.read())
    programs = [scopes._programs(plane) for plane in space.planes
                if plane.name == scopes.HLO_PLANE]
    assert len(programs) == 1 and len(programs[0]) == 4
    assert all(isinstance(k, int) for k in programs[0])


def test_unknown_scopes_are_named():
    op = scopes.Op("fusion.1", 0.0, 1.0, "jit(s)/hvd.ffn/hvd.nope/add", "", 0,
                   0)
    assert scopes.unknown_scopes([op], ("ffn",)) == ["nope"]
    assert set(scopes.account([op], 0.0, 1.0)) == {("nope", "fwd")}
