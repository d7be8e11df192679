"""Device time by block: the op line of a trace added up by the program's
``hvd.<block>`` scopes.

``horovod_tpu.core.timeline.scope(name)`` is ``jax.named_scope("hvd." +
name)``; JAX writes it into an operation's HLO ``op_name`` and the profiler
records that as the stat ``tf_op`` on the *event metadata* of the ``XLA Ops``
line, beside ``hlo_category``, ``flops`` and ``bytes_accessed`` (XLA's own
count).  ``jax.profiler.ProfileData`` hands out an event's own stats only, so
this module reads the ``.xplane.pb`` as the protobuf it is, through a
descriptor built here (the installed profiler plugins ship no ``xplane_pb2``).
Everything after the read is ``trace_reduce``'s: the same clock, the same op
names, the same stretch (``Window.between_reads``).

The rule: an operation belongs to the **innermost** ``hvd.<name>`` segment of
its ``tf_op`` (``jit(step)/jvp(hvd.loss)/M/layer_1/hvd.ffn/dot_general`` is
``ffn``; flax's module names around it are incidental), to ``collective`` if
its ``hlo_category`` is one whatever its scope (the partitioner's collectives
inherit the scope of the operation they complete), and to ``unscoped`` if it
has none.  Its direction is ``bwd`` under ``transpose(`` and ``fwd``
otherwise; ``optimizer``, ``fuse``, ``allreduce`` and ``collective`` have
none.

XLA's own instructions carry no name of JAX's: the copies of layout
assignment, the asynchronous copies into fast memory, fusions it merges, the
kernels it expands an operation into (7 to 27% of a sparse step's time on a
v5e: ``PERF.md``, PR 36).  The trace holds each program's HLO (plane
``/host:metadata``, stat ``Hlo Proto``), so such an instruction is **adopted**:
it takes the name of what it calls (a fusion's root, else the commonest scoped
name inside), else of its nearest user, else of its nearest operand
(:class:`Program`), and where XLA renames what it expands (``ragged-dot-*``)
the block comes from :data:`XLA_RENAMED`, without a direction.  A row says how much of its time
came that way.

Two reductions carry the harness's signature (``readers.REDUCTIONS``) and are
in none of its tables yet: they need ``ctx["xplane"]``, the path of the file
``ctx["window"]`` was cut from, which ``worker.py`` does not hand on today;
without it they find nothing to read and return None.  ``tools/
step_account.py`` prints the whole account of a kept trace.
"""

from __future__ import annotations

import collections
import functools
import re

from chip_bench import trace_reduce

UNSCOPED = "unscoped"
COLLECTIVE = "collective"
# The scopes whose operations run in no direction of a backward pass.
NO_DIRECTION = ("optimizer", "fuse", "allreduce", COLLECTIVE)
_SEGMENT = re.compile(r"hvd\.([a-z0-9_]+(?:\.[a-z0-9_]+)*)")
_COLLECTIVE = re.compile(trace_reduce.COLLECTIVE)

# What XLA's own expansion of an operation is called on the op line (its
# ``tf_op`` is then that name and no path), and the one block whose code
# emits that operation: ``lax.ragged_dot`` is ``moe_ffn``'s grouped product.
XLA_RENAMED = ((re.compile(r"^ragged-dot"), "moe.experts"),)
# How far an instruction without a name looks for one among its users and
# operands.
_HOPS = 4

# ``adopted``: the ``op_name`` an instruction without a scope takes from the
# HLO around it ("" where it has one of its own, or finds none).  ``also``:
# the other blocks whose operations XLA fused into it: a fusion is one
# operation under one name, and its time cannot be taken apart.
Op = collections.namedtuple(
    "Op", "name start end tf_op hlo_category flops bytes_accessed adopted "
    "also", defaults=("", ()))


def segments(tf_op):
    """Every ``hvd.<name>`` of an ``op_name``, outermost first.  Where XLA
    merged operations it joins their names with ``;``: the first that carries
    a scope speaks for the operation."""
    for part in tf_op.split(";"):
        found = _SEGMENT.findall(part)
        if found:
            return found
    return []


def block(tf_op):
    """The innermost scope of an ``op_name``, without its ``hvd.``."""
    found = segments(tf_op)
    return found[-1] if found else UNSCOPED


def direction(tf_op):
    """``bwd`` for an operation of a transposed (backward) computation,
    ``fwd`` for any other, ``""`` under a scope that has no direction."""
    if block(tf_op) in NO_DIRECTION:
        return ""
    return "bwd" if "transpose(" in tf_op else "fwd"


def row_of(op):
    """(block, direction) of one :class:`Op`."""
    if _COLLECTIVE.search(op.hlo_category) or _COLLECTIVE.search(op.name):
        return COLLECTIVE, ""
    if segments(op.tf_op):
        return block(op.tf_op), direction(op.tf_op)
    for pattern, renamed in XLA_RENAMED:
        if pattern.search(op.name):
            # Its neighbours say neither the block (a grouped product's user
            # is the next block's) nor, reliably, the direction.
            return renamed, ""
    if op.adopted:
        return block(op.adopted), direction(op.adopted)
    return UNSCOPED, direction(op.tf_op)


def is_adopted(op):
    """Whether ``row_of`` took the row from the HLO around the operation."""
    return not segments(op.tf_op) and row_of(op)[0] not in (UNSCOPED,
                                                             COLLECTIVE)


# -- the file ------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _messages():
    """The message classes of an ``.xplane.pb`` and of the HLO it carries
    (tsl's ``xplane.proto``, xla's ``hlo.proto``: the fields this module
    reads, under their numbers; a map is its entries)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    I, S, M = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_MESSAGE
    package = "chip_bench_xplane"
    messages = {
        "XStat": [("metadata_id", 1, I), ("double_value", 2, F.TYPE_DOUBLE),
                  ("uint64_value", 3, F.TYPE_UINT64), ("int64_value", 4, I),
                  ("str_value", 5, S), ("bytes_value", 6, F.TYPE_BYTES),
                  ("ref_value", 7, F.TYPE_UINT64)],
        "XEvent": [("metadata_id", 1, I), ("offset_ps", 2, I),
                   ("duration_ps", 3, I)],
        "XLine": [("name", 2, S), ("timestamp_ns", 3, I),
                  ("events", 4, M, "XEvent")],
        "XEventMetadata": [("name", 2, S), ("stats", 5, M, "XStat")],
        "XStatMetadata": [("name", 2, S)],
        "EventMetadataEntry": [("key", 1, I),
                               ("value", 2, M, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, I),
                              ("value", 2, M, "XStatMetadata")],
        "XPlane": [("name", 2, S), ("lines", 3, M, "XLine"),
                   ("event_metadata", 4, M, "EventMetadataEntry"),
                   ("stat_metadata", 5, M, "StatMetadataEntry")],
        "XSpace": [("planes", 1, M, "XPlane")],
        # xla's hlo.proto, as far as an instruction's name, its neighbours and
        # what it calls.
        "OpMetadata": [("op_name", 2, S)],
        "HloInstruction": [("name", 1, S),
                           ("metadata", 7, M, "OpMetadata"), ("id", 35, I),
                           ("operand_ids", 36, I),
                           ("called_computation_ids", 38, I)],
        "HloComputation": [("instructions", 2, M, "HloInstruction"),
                           ("id", 5, I), ("root_id", 6, I)],
        "HloModule": [("computations", 3, M, "HloComputation")],
        "HloProto": [("hlo_module", 1, M, "HloModule")],
    }
    repeated = {"events", "stats", "lines", "event_metadata", "stat_metadata",
                "planes", "operand_ids", "called_computation_ids",
                "instructions", "computations"}
    fd = descriptor_pb2.FileDescriptorProto(
        name=package + ".proto", package=package, syntax="proto3")
    for name, fields in messages.items():
        message = fd.message_type.add(name=name)
        for field_name, number, kind, *of in fields:
            field = message.field.add(
                name=field_name, number=number, type=kind,
                label=F.LABEL_REPEATED if field_name in repeated
                else F.LABEL_OPTIONAL)
            if of:
                field.type_name = f".{package}.{of[0]}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.{name}"))
        for name in ("XSpace", "HloProto")}


class Program:
    """One HLO module of a trace: for an instruction whose ``op_name`` holds
    no scope, the nearest one that does."""

    def __init__(self, module):
        self._by_id, self._by_name, self._roots = {}, {}, {}
        self._users = collections.defaultdict(list)
        self._inside = {}
        for computation in module.computations:
            self._roots[computation.id] = computation.root_id
            self._inside[computation.id] = computation.instructions
            for ins in computation.instructions:
                self._by_id[ins.id] = ins
                self._by_name[ins.name] = ins
                for operand in ins.operand_ids:
                    self._users[operand].append(ins.id)

    def adopted(self, name):
        """The scoped ``op_name`` the instruction called ``name`` takes from
        the HLO around it, or "": nearest first, a user before an operand."""
        ins = self._by_name.get(name)
        if ins is None:
            return ""
        queue, seen = collections.deque([(ins.id, _HOPS)]), {ins.id}
        while queue:
            ident, hops = queue.popleft()
            ins = self._by_id.get(ident)
            if ins is None:
                continue
            found = ins.metadata.op_name if segments(ins.metadata.op_name) \
                else self._called(ins)
            if found:
                return found
            for near in (*self._users[ident], *ins.operand_ids) if hops \
                    else ():
                if near not in seen:
                    seen.add(near)
                    queue.append((near, hops - 1))
        return ""

    def _scoped_inside(self, ins):
        """The scoped ``op_name``s of the instructions inside what ``ins``
        calls (a fusion's body)."""
        return [i.metadata.op_name for called in ins.called_computation_ids
                for i in self._inside.get(called, ())
                if segments(i.metadata.op_name)]

    def inside(self, name):
        """The blocks XLA fused into the instruction called ``name``,
        sorted."""
        ins = self._by_name.get(name)
        return () if ins is None else tuple(sorted(
            {block(n) for n in self._scoped_inside(ins)}))

    def _called(self, ins):
        """The name an instruction takes from what it calls: a root's, else
        the commonest scoped name inside."""
        for called in ins.called_computation_ids:
            root = self._by_id.get(self._roots.get(called))
            if root is not None and segments(root.metadata.op_name):
                return root.metadata.op_name
        names = collections.Counter(self._scoped_inside(ins))
        return names.most_common(1)[0][0] if names else ""


HLO_PLANE = "/host:metadata"


def _programs(plane):
    """{program id: :class:`Program`} of the trace's HLO plane: an entry a
    module, named ``<module>(<program id>)``, its ``Hlo Proto`` stat the
    serialized ``HloProto``."""
    out = {}
    for entry in plane.event_metadata:
        m = re.search(r"\((\d+)\)$", entry.value.name)
        for stat in entry.value.stats:
            if m and stat.bytes_value:
                proto = _messages()["HloProto"]()
                proto.ParseFromString(stat.bytes_value)
                out[int(m.group(1))] = Program(proto.hlo_module)
    return out


def _stats(metadata, stat_names):
    """{stat name: value} of one event metadata; a ``ref_value`` names another
    stat metadata, whose name is the string."""
    out = {}
    for stat in metadata.stats:
        if stat.ref_value:
            value = stat_names.get(stat.ref_value, "")
        else:
            value = stat.str_value or stat.int64_value or stat.uint64_value \
                or stat.double_value
        out[stat_names.get(stat.metadata_id, "")] = value
    return out


@functools.lru_cache(maxsize=2)
def device_ops(path, chip=None):
    """One chip's op line (the lowest-numbered device plane unless ``chip``
    is given) as a tuple of :class:`Op` in start order, names and seconds as
    ``trace_reduce.Trace`` has them."""
    space = _messages()["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes, programs = {}, {}
    for plane in space.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            planes[int(m.group(1))] = plane
        elif plane.name == HLO_PLANE:
            programs = _programs(plane)
    if not planes:
        return ()
    plane = planes[min(planes) if chip is None else chip]
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    described = {}
    for entry in plane.event_metadata:
        stats = _stats(entry.value, stat_names)
        name, tf_op = trace_reduce.op_name(entry.value.name), \
            str(stats.get("tf_op", ""))
        program = programs.get(stats.get("program_id"))
        adopted = program.adopted(name) \
            if program and not segments(tf_op) else ""
        mine = block(tf_op if segments(tf_op) else adopted)
        described[entry.key] = (
            name, tf_op, str(stats.get("hlo_category", "")),
            int(stats.get("flops", 0)), int(stats.get("bytes_accessed", 0)),
            adopted, tuple(b for b in program.inside(name) if b != mine)
            if program else ())
    ops = []
    for line in plane.lines:
        if line.name != trace_reduce.OP_LINE:
            continue
        for e in line.events:
            # Whole nanoseconds, cut as ``ProfileData`` cuts them.
            start_ns = float(line.timestamp_ns + e.offset_ps // 1000)
            name, *rest = described[e.metadata_id]
            ops.append(Op(name, start_ns * 1e-9,
                          (start_ns + e.duration_ps // 1000) * 1e-9, *rest))
    return tuple(sorted(ops, key=lambda op: op.start))


# -- the account ---------------------------------------------------------------


class Row:
    """What one (block, direction) holds of a stretch: seconds on the op line,
    operations (counted where they end, as ``Window.op_count`` does), XLA's
    count of their floating-point operations and bytes, the seconds XLA
    counted nothing of, the seconds of instructions that took the row from
    the HLO around them, the seconds of fusions that also hold operations of
    another block (by that block), and the seconds by op name and by
    ``tf_op`` prefix."""

    def __init__(self):
        self.seconds = 0.0
        self.ops = 0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.uncounted_s = 0.0
        self.adopted_s = 0.0
        self.also = collections.Counter()
        self.by_name = collections.Counter()
        self.by_prefix = collections.Counter()

    def add(self, op, lo, hi):
        seconds = min(op.end, hi) - max(op.start, lo)
        # An operation cut by the stretch's edge gives its share of the
        # counts: a row's rate is then of the time it shows.
        share = seconds / (op.end - op.start) if op.end > op.start else 1.0
        self.seconds += seconds
        self.ops += op.end <= hi
        self.flops += share * op.flops
        self.bytes_accessed += share * op.bytes_accessed
        if not op.flops and not op.bytes_accessed:
            # A pallas kernel is a custom call to XLA, which counts nothing
            # of what it does.
            self.uncounted_s += seconds
        if is_adopted(op):
            self.adopted_s += seconds
        for other in op.also:
            self.also[other] += seconds
        self.by_name[re.sub(r"\.\d+$", "", op.name)] += seconds
        self.by_prefix[op.tf_op.rsplit("/", 1)[0] if "/" in op.tf_op
                       else op.tf_op or f"({op.hlo_category})"] += seconds


def account(ops, lo, hi):
    """{(block, direction): :class:`Row`} of the operations that run between
    ``lo`` and ``hi`` seconds, each cut to the stretch."""
    rows = collections.defaultdict(Row)
    for op in ops:
        if min(op.end, hi) > max(op.start, lo):
            rows[row_of(op)].add(op, lo, hi)
    return dict(rows)


def unknown_scopes(ops, known):
    """The ``hvd.`` segments of ``ops`` that ``known`` (``timeline.SCOPES``)
    does not list: the vocabulary is closed."""
    return sorted({s for op in ops for name in (op.tf_op, op.adopted)
                   for s in segments(name)} - set(known))


# -- reductions with the harness's signature ----------------------------------


def _stretch(ctx):
    window, path = ctx.get("window"), ctx.get("xplane")
    if window is None or not window.ops or not path:
        return None
    return window, account(device_ops(path), window.lo, window.hi)


def scope_ms_per_step(p, ctx):
    """Milliseconds a step of the op line under ``p["scope"]``; with
    ``p["direction"]`` (``fwd`` | ``bwd``) that direction alone, with
    ``p["pattern"]`` only the operations whose name matches (a scope's
    kernels apart from the small operations beside them).  None where the run
    kept no trace or the program wrote no such scope: the metric is then left
    out of the line."""
    found = _stretch(ctx)
    if found is None:
        return None
    window, rows = found
    mine = [row for (name, way), row in rows.items() if name == p["scope"]
            and p.get("direction") in (None, way)]
    if not mine:
        return None
    if "pattern" in p:
        rx = re.compile(p["pattern"])
        seconds = sum(s for row in mine for name, s in row.by_name.items()
                      if rx.search(name))
    else:
        seconds = sum(row.seconds for row in mine)
    return 1e3 * seconds / window.steps


def unscoped_pct(p, ctx):
    """The share of the device's busy time under no scope, in percent; None
    where no operation of the stretch carries one (a program from before the
    scopes, or an executable from a compile cache that holds it)."""
    found = _stretch(ctx)
    if found is None:
        return None
    window, rows = found
    if all(name in (UNSCOPED, COLLECTIVE) for name, _ in rows):
        return None
    unscoped = sum(row.seconds for (name, _), row in rows.items()
                   if name == UNSCOPED)
    return 100.0 * unscoped / window.busy_s()


REDUCTIONS = {
    "trace_scope_ms_per_step": scope_ms_per_step,
    "trace_unscoped_pct": unscoped_pct,
}
