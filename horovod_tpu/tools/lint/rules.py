"""The hvd-lint rule set — each rule encodes one invariant this codebase
actually depends on (see module docstrings it references for the why).

Rules are deliberately syntactic and local: they run on a single file's
AST plus a small amount of cross-file state (the fault-site registry, the
fault-injection doc).  False positives are handled by suppression comments
with mandatory justification, not by weakening the rule.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from . import FileContext, Project, Violation

HOROVOD_KNOB_RE = re.compile(r"^HOROVOD_[A-Z0-9_]+$")

#: Terminal attribute/variable names that denote a lock-ish object.  ``cv``
#: and ``cond`` are included so a Condition's no-timeout ``wait`` inside its
#: own ``with cv:`` block is caught too.
LOCK_NAME_RE = re.compile(r"(^|_)(lock|mutex|cv|cond|condition)$",
                          re.IGNORECASE)

ENV_GETTERS = {"get_int", "get_float", "get_bool", "get_str"}


def _terminal_name(node: ast.AST) -> Optional[str]:
    """Last dotted segment of a Name/Attribute chain (``p.send_lock`` ->
    ``send_lock``); None for anything else."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted repr for diagnostics and identity ('self._lock')."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return f"{_dotted(node.value)}.{node.attr}"
    if isinstance(node, ast.Call):
        return f"{_dotted(node.func)}()"
    return "<expr>"


def _is_lockish(node: ast.AST) -> bool:
    name = _terminal_name(node)
    return name is not None and LOCK_NAME_RE.search(name) is not None


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


class Rule:
    code = "HVD???"
    title = ""

    def check(self, ctx: FileContext,
              project: Project) -> Iterator[Violation]:  # pragma: no cover
        raise NotImplementedError

    def _v(self, ctx: FileContext, node: ast.AST, msg: str) -> Violation:
        return Violation(self.code, ctx.path,
                         getattr(node, "lineno", 1),
                         getattr(node, "col_offset", 0), msg)


# ---------------------------------------------------------------------------
# HVD001 — blocking call while holding a lock
# ---------------------------------------------------------------------------

class BlockingUnderLock(Rule):
    """The PR 2 hang-class contract: nothing may block unboundedly while a
    lock is held.  A blocked holder wedges every other thread that needs
    the lock — including the abort path that would have un-wedged it.

    Detected blocking shapes (inside a ``with <lock>:`` body, or between a
    lock's ``.acquire()`` and ``.release()`` in the same function):

    - ``time.sleep(...)``
    - raw socket ops (``recv``/``recv_into``/``accept``/``send``/
      ``sendall`` on a receiver whose name mentions sock/listener/conn)
    - ``.join()`` / ``.wait()`` / ``.wait_for(pred)`` / ``.result()`` /
      ``.communicate()`` without a timeout
    - ``subprocess.run/call/check_call/check_output`` without ``timeout=``
    - ``.get()`` with no args on a queue-named receiver
    """

    code = "HVD001"
    title = "blocking call while holding a lock"

    _SOCK_RECEIVER_RE = re.compile(r"(sock|listener|conn)", re.IGNORECASE)
    _SOCK_METHODS = {"recv", "recv_into", "recvfrom", "accept",
                     "send", "sendall", "sendto"}
    _SUBPROCESS_FUNCS = {"run", "call", "check_call", "check_output"}

    def check(self, ctx, project):
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_function(ctx, node)

    def _check_function(self, ctx, fn) -> Iterator[Violation]:
        held: List[str] = []
        yield from self._visit_stmts(ctx, fn.body, held)

    def _visit_stmts(self, ctx, stmts, held) -> Iterator[Violation]:
        for stmt in stmts:
            yield from self._visit_stmt(ctx, stmt, held)

    def _visit_stmt(self, ctx, stmt, held) -> Iterator[Violation]:
        # Track acquire()/release() pairs in source order.  This is a lint
        # approximation (no path sensitivity), which is exactly what we
        # want: code whose lock extent is hard to see statically is code
        # that should be rewritten as a ``with`` block.
        for call in self._calls_in(stmt):
            name = _terminal_name(call.func)
            if name == "acquire" and isinstance(call.func, ast.Attribute) \
                    and _is_lockish(call.func.value):
                lock = _dotted(call.func.value)
                if lock not in held:
                    held.append(lock)
            elif name == "release" and isinstance(call.func, ast.Attribute) \
                    and _is_lockish(call.func.value):
                lock = _dotted(call.func.value)
                if lock in held:
                    held.remove(lock)

        if isinstance(stmt, ast.With):
            pushed = []
            for item in stmt.items:
                cm = item.context_expr
                if _is_lockish(cm):
                    pushed.append(_dotted(cm))
            held.extend(pushed)
            yield from self._visit_stmts(ctx, stmt.body, held)
            for name in pushed:
                if name in held:
                    held.remove(name)
            return

        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            # A nested def runs later, on some other call stack: the
            # enclosing lock scope does not apply; its own body is visited
            # by the module-level walk.
            return

        if held:
            yield from self._flag_blocking(ctx, stmt, held)

        for attr in ("body", "orelse", "finalbody"):
            for sub in getattr(stmt, attr, []):
                yield from self._visit_stmt(ctx, sub, held)
        for handler in getattr(stmt, "handlers", []):
            yield from self._visit_stmts(ctx, handler.body, held)

    def _calls_in(self, stmt) -> Iterator[ast.Call]:
        """Calls in the statement's own expressions (not sub-statements,
        not nested defs)."""
        for field_ in ast.iter_fields(stmt):
            _, value = field_
            values = value if isinstance(value, list) else [value]
            for v in values:
                if isinstance(v, ast.stmt) or not isinstance(v, ast.AST):
                    continue
                for sub in ast.walk(v):
                    if isinstance(sub, ast.Call):
                        yield sub

    def _flag_blocking(self, ctx, stmt, held) -> Iterator[Violation]:
        lock_desc = ", ".join(held)
        for call in self._calls_in(stmt):
            msg = self._blocking_reason(call)
            if msg:
                yield self._v(
                    ctx, call,
                    f"{msg} while holding {lock_desc}; a blocked holder "
                    "wedges every thread that needs the lock (move the "
                    "blocking call outside the lock scope or bound it)")

    def _blocking_reason(self, call: ast.Call) -> Optional[str]:
        func = call.func
        name = _terminal_name(func)
        if name is None:
            return None
        has_timeout_kw = _kw(call, "timeout") is not None

        if name == "sleep":
            recv = func.value if isinstance(func, ast.Attribute) else None
            if recv is None or _terminal_name(recv) == "time":
                return "time.sleep"
        if isinstance(func, ast.Attribute):
            recv_name = _dotted(func.value)
            if name in self._SOCK_METHODS \
                    and self._SOCK_RECEIVER_RE.search(recv_name):
                return f"raw socket .{name}()"
            if name == "join" and not call.args and not has_timeout_kw:
                # str.join always passes an iterable positionally, so a
                # zero-positional-arg join is a thread/process join.
                return "unbounded .join()"
            if name in ("wait", "communicate", "result") \
                    and not call.args and not has_timeout_kw:
                return f"unbounded .{name}()"
            if name == "wait_for" and len(call.args) <= 1 \
                    and not has_timeout_kw:
                return "unbounded .wait_for()"
            if name == "get" and not call.args and not has_timeout_kw \
                    and _kw(call, "block") is None \
                    and re.search(r"(queue|_q)$", recv_name, re.IGNORECASE):
                return "unbounded queue .get()"
            if name in self._SUBPROCESS_FUNCS \
                    and _terminal_name(func.value) == "subprocess" \
                    and not has_timeout_kw:
                return f"subprocess.{name} without timeout"
        return None


# ---------------------------------------------------------------------------
# HVD002 — raw HOROVOD_* env literal outside common/env.py
# ---------------------------------------------------------------------------

class EnvLiteralOutsideRegistry(Rule):
    """``common/env.py``'s module docstring promises it is the single
    source of config truth.  A ``HOROVOD_*`` knob read (or written)
    through a string literal anywhere else forks that truth: the knob is
    invisible to the registry, its default gets duplicated, and a typo'd
    name silently reads nothing."""

    code = "HVD002"
    title = "raw HOROVOD_* env literal outside common/env.py"

    def check(self, ctx, project):
        if ctx.rel_path.endswith("common/env.py"):
            return
        for node in ast.walk(ctx.tree):
            lit = self._env_literal(node)
            if lit is not None:
                yield self._v(
                    ctx, node,
                    f"raw env access of {lit!r}; declare a named constant "
                    "in horovod_tpu/common/env.py and reference it "
                    "(single config-truth contract)")

    def _env_literal(self, node: ast.AST) -> Optional[str]:
        # os.environ["HOROVOD_X"] loads/stores/deletes
        if isinstance(node, ast.Subscript) and self._is_environ(node.value):
            return self._knob(node.slice)
        if not isinstance(node, ast.Call):
            return None
        func = node.func
        name = _terminal_name(func)
        if name in ("get", "setdefault", "pop") \
                and isinstance(func, ast.Attribute) \
                and self._is_environ(func.value) and node.args:
            return self._knob(node.args[0])
        if name == "getenv" and isinstance(func, ast.Attribute) \
                and _terminal_name(func.value) == "os" and node.args:
            return self._knob(node.args[0])
        if name in ENV_GETTERS and node.args:
            return self._knob(node.args[0])
        return None

    @staticmethod
    def _is_environ(node: ast.AST) -> bool:
        return _terminal_name(node) == "environ"

    @staticmethod
    def _knob(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and HOROVOD_KNOB_RE.match(node.value):
            return node.value
        return None


# ---------------------------------------------------------------------------
# HVD003 — fault sites must come from (and be documented in) the registry
# ---------------------------------------------------------------------------

class FaultSiteRegistry(Rule):
    """``faults.inject("tcp.rcv")`` with a typo'd site matches no clause,
    injects nothing, and passes every chaos test vacuously — the exact
    silent failure the fault plane exists to prevent.  Every injected site
    must be a literal found in ``faults.SITES``, and every registry entry
    must appear in ``docs/fault_injection.md`` so operators can discover
    it."""

    code = "HVD003"
    title = "fault site not in faults.SITES / undocumented site"

    def check(self, ctx, project):
        is_registry = ctx.rel_path.endswith("common/faults.py")
        sites = project.fault_sites
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if _terminal_name(func) != "inject":
                continue
            if isinstance(func, ast.Attribute) \
                    and _terminal_name(func.value) != "faults":
                continue
            if not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if sites and arg.value not in sites:
                    yield self._v(
                        ctx, node,
                        f"fault site {arg.value!r} is not registered in "
                        f"faults.SITES (known: {', '.join(sites)}); a "
                        "typo'd site injects nothing and passes chaos "
                        "tests vacuously")
            elif not is_registry:
                yield self._v(
                    ctx, node,
                    "fault site must be a string literal from faults.SITES "
                    "(a computed site defeats static verification)")
        if is_registry:
            doc = project.fault_doc
            seen: Set[str] = set()
            for site in sites:
                if site in seen:
                    yield Violation(self.code, ctx.path, 1, 0,
                                    f"duplicate faults.SITES entry {site!r}")
                seen.add(site)
                if doc and f"`{site}`" not in doc:
                    yield Violation(
                        self.code, ctx.path, 1, 0,
                        f"registered fault site {site!r} is missing from "
                        "docs/fault_injection.md (the site table is the "
                        "operator-facing registry mirror)")


# ---------------------------------------------------------------------------
# HVD004 — swallowed exception in a thread-target/daemon-loop body
# ---------------------------------------------------------------------------

class SwallowedThreadException(Rule):
    """The PR 2 loop-death contract: a background thread that dies (or
    eats an error) silently converts a loud failure into a distributed
    hang.  Every ``except:``/``except Exception`` in a thread-run body
    must log, re-raise, or abort-broadcast."""

    code = "HVD004"
    title = "swallowed exception in thread-target/daemon-loop body"

    _LOG_METHODS = {"error", "warning", "exception", "critical",
                    "info", "debug", "log"}

    def check(self, ctx, project):
        targets = self._thread_target_names(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not (node.name in targets or node.name.endswith("_loop")
                    or self._is_thread_run(ctx.tree, node)):
                continue
            for handler in self._handlers_in(node):
                if self._is_broad(handler) \
                        and not self._handled_loudly(handler):
                    yield self._v(
                        ctx, handler,
                        f"broad exception swallowed in thread body "
                        f"{node.name!r}: log it, re-raise, or "
                        "abort-broadcast (silent loop death = "
                        "distributed hang)")

    def _thread_target_names(self, tree) -> Set[str]:
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and _terminal_name(node.func) == "Thread":
                tgt = _kw(node, "target")
                if tgt is not None:
                    name = _terminal_name(tgt)
                    if name:
                        names.add(name)
        return names

    def _is_thread_run(self, tree, fn) -> bool:
        """``run`` methods of classes deriving from Thread."""
        if fn.name != "run":
            return False
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and fn in node.body:
                return any(_terminal_name(b) == "Thread" for b in node.bases)
        return False

    def _handlers_in(self, fn) -> Iterator[ast.ExceptHandler]:
        # Manual walk that does NOT descend into nested defs: a nested
        # function gets its own assessment iff it is itself a thread body.
        stack = list(fn.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                continue
            if isinstance(node, ast.Try):
                yield from node.handlers
            stack.extend(ast.iter_child_nodes(node))

    @staticmethod
    def _is_broad(handler: ast.ExceptHandler) -> bool:
        if handler.type is None:
            return True
        names = []
        types = handler.type.elts if isinstance(handler.type, ast.Tuple) \
            else [handler.type]
        for t in types:
            names.append(_terminal_name(t))
        return "Exception" in names or "BaseException" in names

    def _handled_loudly(self, handler: ast.ExceptHandler) -> bool:
        for node in ast.walk(handler):
            if isinstance(node, ast.Raise):
                return True
            if isinstance(node, ast.Call):
                name = _terminal_name(node.func)
                if name and "abort" in name.lower():
                    return True
                if isinstance(node.func, ast.Attribute) \
                        and name in self._LOG_METHODS:
                    recv = _dotted(node.func.value)
                    if "log" in recv.lower():
                        return True
            # Stash-and-surface: the bound exception object is READ in the
            # handler body (appended to an error list, assigned to an
            # attribute the waiting parent re-raises, ...).  Capturing the
            # exception for propagation is not a silent swallow.
            if handler.name and isinstance(node, ast.Name) \
                    and node.id == handler.name \
                    and isinstance(node.ctx, ast.Load):
                return True
        return False


# ---------------------------------------------------------------------------
# HVD005 — control-frame wire-tag invariants (core/messages.py)
# ---------------------------------------------------------------------------

class WireTagInvariants(Rule):
    """Frames are distinguished on the wire ONLY by their leading magic,
    and the transport's frame header is ``<Q len|flags><I crc32>`` — the
    length word's top bit reserved for control frames (AbortFrame), the
    CRC field owned by the transport layer alone.  Two classes sharing a
    magic, a frame class without one, messages.py reaching for the
    control bit or computing its own wire CRC, or the header registry's
    structs drifting from the documented layout all produce
    positional-framing desyncs (or silently unverified bytes) that
    surface as 'survivors read negotiation bytes as tensor data'.

    The header VALUES are checked in ``transport/frame_bits.py``, the
    registry every transport imports from (HVD008 enforces that nothing
    re-derives them elsewhere)."""

    code = "HVD005"
    title = "wire framing invariant (core/messages.py, " \
            "transport/frame_bits.py)"

    #: The frame-header layout contract (docs/integrity.md): the length
    #: word and the CRC field each live in exactly one module-level
    #: struct, with these formats.  Changing either silently desyncs
    #: every peer built from a different revision.
    _HEADER_STRUCTS = {"_LEN": "<Q", "_CRC": "<I"}

    #: The flag-bit reservations (docs/data_plane.md): each must be
    #: declared as ``1 << bit`` so mixed-version skew analysis and the
    #: model checker's wire assumptions stay true by inspection.
    _FLAG_BITS = {"_CTRL_FLAG": 63, "_DEFER_FLAG": 62, "_DIGEST_FLAG": 61}

    def check(self, ctx, project):
        if ctx.rel_path.endswith("transport/frame_bits.py"):
            yield from self._check_transport_header(ctx)
            return
        if not ctx.rel_path.endswith("core/messages.py"):
            return
        magics: Dict[str, Tuple[int, ast.AST]] = {}
        for node in ctx.tree.body:
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) \
                            and tgt.id.endswith("_MAGIC"):
                        try:
                            val = ast.literal_eval(node.value)
                        except ValueError:
                            continue
                        magics[tgt.id] = (val, node)
        by_value: Dict[int, str] = {}
        for name, (val, node) in magics.items():
            if val in by_value:
                yield self._v(
                    ctx, node,
                    f"wire tag {name} duplicates {by_value[val]} "
                    f"(0x{val:08X}); frames become indistinguishable")
            else:
                by_value[val] = name
            if not (0 <= val < 2 ** 32):
                yield self._v(ctx, node,
                              f"wire tag {name} does not fit in the u32 "
                              "magic field")

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(ctx, node, magics)
            lit = self._ctrl_bit_literal(node)
            if lit is not None:
                yield self._v(
                    ctx, lit,
                    "core/messages.py must not touch the length-header top "
                    "bit (1 << 63): it is the transport's control-frame "
                    "flag, reserved as _CTRL_FLAG in "
                    "transport/frame_bits.py")
            if isinstance(node, ast.Call) \
                    and _terminal_name(node.func) == "crc32":
                yield self._v(
                    ctx, node,
                    "core/messages.py must not compute wire CRCs: the "
                    "integrity envelope is the transport's _CRC header "
                    "field (one layer, one owner — a second checksum "
                    "here would drift from it)")

    def _check_transport_header(self, ctx) -> Iterator[Violation]:
        """transport/frame_bits.py owns the frame header: ``_LEN``/
        ``_CRC`` structs with the documented formats, and the flag-bit
        reservations (``_CTRL_FLAG = 1 << 63`` and friends), must all
        exist exactly as declared — the wire contract every peer and
        every doc (docs/integrity.md) assumes."""
        structs: Dict[str, object] = {}
        flags: Dict[str, bool] = {name: False for name in self._FLAG_BITS}
        for node in ctx.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not isinstance(tgt, ast.Name):
                    continue
                v = node.value
                if isinstance(v, ast.Call) \
                        and _terminal_name(v.func) == "Struct" \
                        and v.args and isinstance(v.args[0], ast.Constant):
                    structs[tgt.id] = (v.args[0].value, node)
                bit = self._FLAG_BITS.get(tgt.id)
                if bit is not None \
                        and self._bit_literal(v, bit) is not None:
                    flags[tgt.id] = True
        for name, fmt in self._HEADER_STRUCTS.items():
            got = structs.get(name)
            if got is None:
                yield Violation(
                    self.code, ctx.path, 1, 0,
                    f"transport/frame_bits.py must declare {name} = "
                    f"struct.Struct({fmt!r}) (frame-header layout "
                    "contract: <Q len|flags><I crc32>)")
            elif got[0] != fmt:
                yield self._v(
                    ctx, got[1],
                    f"frame-header struct {name} must use format {fmt!r} "
                    f"(found {got[0]!r}); peers built from a different "
                    "layout desync on every frame")
        for name, bit in self._FLAG_BITS.items():
            if not flags[name]:
                yield Violation(
                    self.code, ctx.path, 1, 0,
                    f"transport/frame_bits.py must reserve length-header "
                    f"bit {bit} as {name} = 1 << {bit} (the flag-lane "
                    "contract mixed-version skew detection depends on)")

    #: every Writer method that appends bytes — the magic must precede
    #: ALL of them, not just the first u32 (a u8 written before the u32
    #: magic still shifts the leading 4 bytes off the tag).
    _WRITER_METHODS = frozenset({
        "u8", "u32", "i32", "i64", "f64",
        "string", "i64_list", "i32_list", "str_list",
    })

    def _check_class(self, ctx, cls, magics) -> Iterator[Violation]:
        to_bytes = None
        for node in cls.body:
            if isinstance(node, ast.FunctionDef) and node.name == "to_bytes":
                to_bytes = node
        if to_bytes is None:
            return
        writes = sorted(
            (node for node in ast.walk(to_bytes)
             if isinstance(node, ast.Call)
             and _terminal_name(node.func) in self._WRITER_METHODS),
            key=lambda n: (n.lineno, n.col_offset))
        if writes:
            first_call = writes[0]
            if _terminal_name(first_call.func) == "u32" and first_call.args:
                first = first_call.args[0]
                if isinstance(first, ast.Name) \
                        and first.id.endswith("_MAGIC"):
                    if first.id not in magics:
                        yield self._v(
                            ctx, first,
                            f"{cls.name}.to_bytes writes undeclared wire "
                            f"tag {first.id}")
                    return
        yield self._v(
            ctx, to_bytes,
            f"{cls.name}.to_bytes must write a module-level *_MAGIC wire "
            "tag as its first field (frames are distinguished only by "
            "their leading magic)")

    @staticmethod
    def _ctrl_bit_literal(node: ast.AST) -> Optional[ast.AST]:
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.LShift) \
                and isinstance(node.right, ast.Constant) \
                and node.right.value == 63:
            return node
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and node.value >= 2 ** 63:
            return node
        return None

    @staticmethod
    def _bit_literal(node: ast.AST, bit: int) -> Optional[ast.AST]:
        """``1 << bit`` (or the equivalent integer constant), exactly."""
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.LShift) \
                and isinstance(node.left, ast.Constant) \
                and node.left.value == 1 \
                and isinstance(node.right, ast.Constant) \
                and node.right.value == bit:
            return node
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and node.value == 2 ** bit:
            return node
        return None


# ---------------------------------------------------------------------------
# HVD006 — anonymous threads
# ---------------------------------------------------------------------------

class AnonymousThread(Rule):
    """Lockdep reports, the stall inspector, and py-spy dumps attribute
    work by thread name; an anonymous ``Thread-12`` is undebuggable in a
    process that runs a dozen daemons.  Every thread must be named (and
    every ThreadPoolExecutor must set ``thread_name_prefix``)."""

    code = "HVD006"
    title = "anonymous thread (threading.Thread without name=)"

    def check(self, ctx, project):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef) \
                    and any(_terminal_name(b) == "Thread"
                            for b in node.bases):
                yield from self._check_subclass(ctx, node)
            if not isinstance(node, ast.Call):
                continue
            name = _terminal_name(node.func)
            if name == "Thread" and _kw(node, "target") is not None \
                    and _kw(node, "name") is None:
                yield self._v(
                    ctx, node,
                    "thread has no name=; lockdep and the stall inspector "
                    "cannot attribute an anonymous Thread-N")
            if name == "ThreadPoolExecutor" \
                    and _kw(node, "thread_name_prefix") is None:
                yield self._v(
                    ctx, node,
                    "ThreadPoolExecutor without thread_name_prefix=; "
                    "worker threads become anonymous")

    def _check_subclass(self, ctx, cls) -> Iterator[Violation]:
        """A Thread subclass escapes the Thread(target=...) check, so its
        __init__ must name the thread itself: either pass name= through
        super().__init__/Thread.__init__ or assign self.name."""
        init = None
        for node in cls.body:
            if isinstance(node, ast.FunctionDef) and node.name == "__init__":
                init = node
        if init is None:
            yield self._v(
                ctx, cls,
                f"Thread subclass {cls.name} has no __init__ passing "
                "name=; its instances are anonymous Thread-N")
            return
        for node in ast.walk(init):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute) \
                            and tgt.attr == "name" \
                            and isinstance(tgt.value, ast.Name) \
                            and tgt.value.id == "self":
                        return
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "__init__" \
                    and _kw(node, "name") is not None:
                return
        yield self._v(
            ctx, init,
            f"{cls.name}.__init__ neither passes name= to the Thread "
            "base nor assigns self.name; instances are anonymous "
            "Thread-N")


# ---------------------------------------------------------------------------
# HVD007 — metric names must come from (and be documented in) the catalog
# ---------------------------------------------------------------------------

class MetricCatalogRule(Rule):
    """``metrics.inc("collectve_latency...")`` with a typo'd name records
    into a series nobody reads — dashboards and the overhead guard pass
    vacuously, the exact silent failure HVD003 closes for fault sites.
    Every name fed to ``metrics.inc``/``set_gauge``/``observe`` (and to
    the ``phase_stats``/``wire_stats`` ``add`` accumulators the registry
    absorbs as views, or to ``timeline.phase(...)``, which feeds
    ``phase_stats``) must be a literal found in ``core/metrics.py``'s
    ``CATALOG``, and every catalog entry must appear in
    ``docs/observability.md`` so operators can discover it."""

    code = "HVD007"
    title = "metric name not in metrics CATALOG / undocumented metric"

    _REG_FUNCS = frozenset({"inc", "set_gauge", "observe"})
    _REG_RECEIVERS = frozenset({"metrics", "registry"})
    _STATS_RECEIVERS = frozenset({"wire_stats", "phase_stats"})

    def check(self, ctx, project):
        is_registry = ctx.rel_path.endswith("core/metrics.py")
        names = project.metric_catalog
        if is_registry:
            yield from self._check_registry(ctx, names, project)
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            func = node.func
            fname = _terminal_name(func)
            recv = _terminal_name(func.value) \
                if isinstance(func, ast.Attribute) else None
            if fname in self._REG_FUNCS and recv in self._REG_RECEIVERS:
                pass
            elif fname == "add" and recv in self._STATS_RECEIVERS:
                pass
            elif fname == "phase" and recv in (None, "timeline",
                                               "timeline_mod"):
                pass  # ``with phase("..."):`` feeds phase_stats
            else:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if names and arg.value not in names:
                    yield self._v(
                        ctx, node,
                        f"metric name {arg.value!r} is not declared in "
                        "core/metrics.py CATALOG; a typo'd name records "
                        "into a series nobody reads")
            else:
                yield self._v(
                    ctx, node,
                    "metric name must be a string literal from the "
                    "core/metrics.py CATALOG (a computed name defeats "
                    "static verification)")

    def _check_registry(self, ctx, names, project) -> Iterator[Violation]:
        doc = project.metrics_doc
        seen: Set[str] = set()
        for name in names:
            if name in seen:
                yield Violation(self.code, ctx.path, 1, 0,
                                f"duplicate CATALOG entry {name!r}")
            seen.add(name)
            if doc and f"`{name}`" not in doc:
                yield Violation(
                    self.code, ctx.path, 1, 0,
                    f"cataloged metric {name!r} is missing from "
                    "docs/observability.md (the catalog table is the "
                    "operator-facing registry mirror)")


# ---------------------------------------------------------------------------
# HVD008 — frame-header bit literals live only in transport/frame_bits.py
# ---------------------------------------------------------------------------

class FrameBitRegistry(Rule):
    """The length word's top byte (bits 56-63) is the wire flag/dtype
    lane: control, digest-deferred, digest-check, and the cast-on-the-
    wire dtype code.  Those positions are the cross-transport,
    cross-VERSION contract — tcp and shm must agree with each other and
    with every older peer — so they are defined exactly once, in
    ``transport/frame_bits.py``, and imported everywhere else.  A ``<<
    56``..``<< 63`` literal (or a re-binding of a registry name) in any
    other module is a second derivation of the same bit position: the
    pre-extraction tree had tcp.py owning the bits while shm.py
    re-derived some and imported the rest, which is exactly how framing
    contracts drift apart."""

    code = "HVD008"
    title = "frame-header bit literal outside transport/frame_bits.py"

    #: Names frame_bits.py exports; re-binding one elsewhere forks the
    #: registry even without a raw bit literal.
    _REGISTRY_NAMES = frozenset({
        "_LEN", "_CRC", "_CTRL_FLAG", "_DEFER_FLAG", "_DIGEST_FLAG",
        "_WIRE_DTYPE_SHIFT", "_WIRE_DTYPE_MASK", "_FLAGS_MASK",
        "_DIGEST_PAYLOAD", "_FrameHeader", "_MAX_FRAME_BYTES",
        # wire dtype codes (the 3-bit lane's values): re-binding one
        # outside the registry forks the compression skew contract
        "_WIRE_DTYPE_RAW", "_WIRE_DTYPE_FP16", "_WIRE_DTYPE_BF16",
        "_WIRE_DTYPE_INT8", "_WIRE_DTYPE_ONEBIT", "_WIRE_DTYPE_TOPK",
    })
    _FLAG_BIT_RANGE = range(56, 64)

    def check(self, ctx, project):
        if ctx.rel_path.endswith("transport/frame_bits.py"):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.BinOp) \
                    and isinstance(node.op, ast.LShift) \
                    and isinstance(node.right, ast.Constant) \
                    and isinstance(node.right.value, int) \
                    and node.right.value in self._FLAG_BIT_RANGE:
                yield self._v(
                    ctx, node,
                    f"frame-header bit literal (<< {node.right.value}): "
                    "bits 56-63 of the length word are the wire "
                    "flag/dtype lane, defined once in "
                    "transport/frame_bits.py — import the named constant "
                    "instead of re-deriving the position")
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) \
                            and tgt.id in self._REGISTRY_NAMES:
                        yield self._v(
                            ctx, node,
                            f"re-binding of frame-bit registry name "
                            f"{tgt.id}: transport/frame_bits.py is the "
                            "single source of the frame-header contract; "
                            "import it, don't shadow it")


# ---------------------------------------------------------------------------
# HVD009 — shm control words move only through the accessor helpers
# ---------------------------------------------------------------------------

class ShmAccessorDiscipline(Rule):
    """The shm ring's correctness argument is machine-checked (hvd-mck)
    over the step generators, and the proof only covers accesses the
    model can see.  ``transport/shm.py`` therefore funnels EVERY raw
    struct move against a header offset through four accessors
    (``_load_u64``/``_store_u64``/``_load_u32``/``_store_u32``) so the
    set of shared-memory control-word accesses is closed by
    construction.  A raw ``unpack_from``/``pack_into`` against an
    ``_OFF_*`` constant (or a ``*_head_off``/``*_tail_off``/
    ``*_bell_off``/``*_pid_off`` attribute) anywhere else is a
    shared-memory access the checker never explored — an unverified hole
    in a verified protocol."""

    code = "HVD009"
    title = "raw struct access against shm control-word offsets"

    _ACCESSORS = frozenset({"_load_u64", "_store_u64",
                            "_load_u32", "_store_u32"})
    _STRUCT_METHODS = frozenset({"unpack_from", "pack_into"})
    _OFF_CONST_RE = re.compile(r"^_OFF_[A-Z0-9_]+$")
    _OFF_ATTR_RE = re.compile(r"(^|_)(head|tail|bell|pid)_off$")

    def check(self, ctx, project):
        in_shm = ctx.rel_path.endswith("transport/shm.py")
        yield from self._scan(ctx, ctx.tree, None, in_shm)

    def _scan(self, ctx, node, fn_name, in_shm) -> Iterator[Violation]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._scan(ctx, child, child.name, in_shm)
                continue
            if isinstance(child, ast.Call) \
                    and _terminal_name(child.func) in self._STRUCT_METHODS \
                    and not (in_shm and fn_name in self._ACCESSORS):
                yield from self._check_call(ctx, child, in_shm)
            yield from self._scan(ctx, child, fn_name, in_shm)

    def _check_call(self, ctx, call, in_shm) -> Iterator[Violation]:
        offending = None
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            for sub in ast.walk(arg):
                name = _terminal_name(sub)
                if name is not None and self._is_offset_name(name):
                    offending = name
                    break
            if offending:
                break
        method = _terminal_name(call.func)
        if offending:
            yield self._v(
                ctx, call,
                f"raw {method} against shm header offset {offending}: "
                "control words move only through the "
                "_load_u64/_store_u64/_load_u32/_store_u32 accessors "
                "(the model-checked access set is closed by "
                "construction)")
        elif in_shm:
            yield self._v(
                ctx, call,
                f"raw struct {method} in transport/shm.py outside the "
                "control-word accessors: every shared-memory struct move "
                "must go through _load_u64/_store_u64/_load_u32/"
                "_store_u32 so hvd-mck's access model stays exhaustive")

    def _is_offset_name(self, name: str) -> bool:
        return self._OFF_CONST_RE.match(name) is not None \
            or self._OFF_ATTR_RE.search(name) is not None


# ---------------------------------------------------------------------------
# HVD010 — rendezvous scope names come from transport/scopes.py
# ---------------------------------------------------------------------------

class ScopeNameRegistry(Rule):
    """A rendezvous scope name is a wire contract between the driver, the
    workers, and the store server — three parties that never share code
    at runtime, so a typo reads an empty scope and times out instead of
    failing loudly.  ``transport/scopes.py`` is the single source of
    those names; everything else imports the constant.  A registered
    scope name appearing as a STRING LITERAL in a scope position
    elsewhere (first argument of a store ``set``/``get``/``delete``/
    ``keys``/``wait`` call, or the scope slot of a batch op tuple) is a
    second spelling of the same contract — exactly how ``"epoch_ack"``
    drifted into three modules before the registry existed.  Re-binding
    a ``*_SCOPE`` name to a registered value forks it the same way."""

    code = "HVD010"
    title = "rendezvous scope literal outside transport/scopes.py"

    #: Store-API methods whose FIRST positional argument is a scope,
    #: mapped to the minimum positional arity of the STORE signature —
    #: ``set(scope, key, value)`` has 3, ``get(scope, key)`` has 2,
    #: ``keys(scope)`` has 1.  The arity gate is what keeps a plain dict
    #: lookup like ``fetched.get("epoch_ack")`` (one arg: a local dict
    #: key, not a wire scope) out of the rule's blast radius.
    _SCOPE_CALLS = {
        "set": 3, "store_set": 3,
        "get": 2, "delete": 2, "wait": 2,
        "store_get": 2, "store_delete": 2,
        "keys": 1, "store_keys": 1,
    }
    #: Batch op verbs: ``(verb, scope, key[, value])`` tuples.
    _BATCH_VERBS = frozenset({"set", "get", "delete", "keys"})

    def check(self, ctx, project):
        if ctx.rel_path.endswith("transport/scopes.py"):
            return
        scopes = frozenset(project.scope_registry)
        if not scopes:
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                name = _terminal_name(node.func)
                if name in self._SCOPE_CALLS \
                        and len(node.args) >= self._SCOPE_CALLS[name] \
                        and isinstance(node.args[0], ast.Constant) \
                        and node.args[0].value in scopes:
                    yield self._v(
                        ctx, node,
                        f"scope literal {node.args[0].value!r} in "
                        f"{name}() call: scope names are a wire contract "
                        "defined once in transport/scopes.py — import "
                        "the constant instead of re-spelling it")
            elif isinstance(node, (ast.Tuple, ast.List)) \
                    and len(node.elts) >= 2 \
                    and isinstance(node.elts[0], ast.Constant) \
                    and node.elts[0].value in self._BATCH_VERBS \
                    and isinstance(node.elts[1], ast.Constant) \
                    and node.elts[1].value in scopes:
                yield self._v(
                    ctx, node,
                    f"scope literal {node.elts[1].value!r} in batch op "
                    f"tuple ({node.elts[0].value!r}, ...): import the "
                    "constant from transport/scopes.py instead of "
                    "re-spelling the wire contract")
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Constant) \
                    and node.value.value in scopes:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) \
                            and tgt.id.endswith("_SCOPE"):
                        yield self._v(
                            ctx, node,
                            f"re-binding of scope name {tgt.id} = "
                            f"{node.value.value!r}: transport/scopes.py "
                            "is the single source of scope names; "
                            "import it, don't shadow it")


ALL_RULES: Tuple[Rule, ...] = (
    BlockingUnderLock(),
    EnvLiteralOutsideRegistry(),
    FaultSiteRegistry(),
    SwallowedThreadException(),
    WireTagInvariants(),
    AnonymousThread(),
    MetricCatalogRule(),
    FrameBitRegistry(),
    ShmAccessorDiscipline(),
    ScopeNameRegistry(),
)

RULE_CODES = frozenset(r.code for r in ALL_RULES) | {"HVD000"}
