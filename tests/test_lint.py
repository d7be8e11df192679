"""hvd-lint: per-rule fixtures + the zero-violation contract on the tree.

Every rule gets three fixtures — one violating, one clean, one suppressed
with a justification — so a rule that silently stops firing (or starts
over-firing) fails here, not in review.  The capstone test runs the full
pass over ``horovod_tpu/`` and asserts zero violations: landing a change
that breaks an invariant makes THIS file fail with the right rule code.
"""

from __future__ import annotations

import os
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from horovod_tpu.tools.lint import (  # noqa: E402
    Project,
    lint_paths,
    lint_source,
    main,
)
from horovod_tpu.tools.lint.rules import RULE_CODES  # noqa: E402

PKG = os.path.join(REPO_ROOT, "horovod_tpu")
PROJECT = Project(root=REPO_ROOT)


def run(src: str, path: str = "<fixture>"):
    return lint_source(textwrap.dedent(src), path=path, project=PROJECT)


def codes(violations):
    return sorted({v.code for v in violations})


@pytest.fixture(scope="module")
def tree_violations():
    """One full-tree pass shared by every test that needs it."""
    return lint_paths([PKG], PROJECT)


# ---------------------------------------------------------------------------
# HVD001 — blocking call while holding a lock
# ---------------------------------------------------------------------------

HVD001_WITH = """
    import threading, time
    lock = threading.Lock()
    def f():
        with lock:
            time.sleep(1)
"""

HVD001_ACQUIRE = """
    import time
    class C:
        def f(self):
            self._lock.acquire()
            try:
                data = self.sock.recv(4)
            finally:
                self._lock.release()
"""

HVD001_CLEAN = """
    import threading, time
    lock = threading.Lock()
    def f():
        with lock:
            x = 1
        time.sleep(1)
        done.wait(timeout=5)
"""

HVD001_SUPPRESSED = """
    import threading, time
    lock = threading.Lock()
    def f():
        with lock:
            time.sleep(1)  # hvdlint: disable=HVD001 -- fixture: bounded by test harness
"""


def test_hvd001_with_block():
    vs = run(HVD001_WITH)
    assert codes(vs) == ["HVD001"]
    assert "time.sleep" in vs[0].message


def test_hvd001_acquire_release_region():
    vs = run(HVD001_ACQUIRE)
    assert codes(vs) == ["HVD001"]
    assert "socket" in vs[0].message


def test_hvd001_clean():
    assert run(HVD001_CLEAN) == []


def test_hvd001_suppressed():
    assert run(HVD001_SUPPRESSED) == []


def test_hvd001_string_join_not_flagged():
    # str.join takes a positional iterable; thread joins take none.
    src = """
        import threading
        lock = threading.Lock()
        def f(parts, t):
            with lock:
                s = ",".join(parts)
            t.join()
    """
    assert run(src) == []


# ---------------------------------------------------------------------------
# HVD002 — raw HOROVOD_* env literal outside common/env.py
# ---------------------------------------------------------------------------

HVD002_VIOLATING = """
    import os
    a = os.environ.get("HOROVOD_FOO")
    b = os.getenv("HOROVOD_BAR", "1")
    os.environ["HOROVOD_BAZ"] = "x"
    c = env_mod.get_int("HOROVOD_QUX", 0)
"""

HVD002_CLEAN = """
    import os
    from horovod_tpu.common import env as env_mod
    a = env_mod.get_str(env_mod.HOROVOD_ELASTIC)
    b = os.environ.get(env_mod.HOROVOD_RANK)
    c = os.environ.get("NOT_A_KNOB")
"""

HVD002_SUPPRESSED = """
    import os
    a = os.environ.get("HOROVOD_FOO")  # hvdlint: disable=HVD002 -- fixture: pretend legacy shim
"""


def test_hvd002_violating():
    vs = run(HVD002_VIOLATING)
    assert codes(vs) == ["HVD002"]
    assert len(vs) == 4
    assert {"HOROVOD_FOO", "HOROVOD_BAR", "HOROVOD_BAZ", "HOROVOD_QUX"} == {
        v.message.split("'")[1] for v in vs}


def test_hvd002_clean():
    assert run(HVD002_CLEAN) == []


def test_hvd002_env_py_itself_exempt():
    path = os.path.join(PKG, "common", "env.py")
    assert run(HVD002_VIOLATING, path=path) == []


def test_hvd002_suppressed():
    assert run(HVD002_SUPPRESSED) == []


# ---------------------------------------------------------------------------
# HVD003 — fault sites
# ---------------------------------------------------------------------------

HVD003_VIOLATING = """
    from horovod_tpu.common import faults
    def f():
        if faults.ACTIVE:
            faults.inject("tcp.rcv")
"""

HVD003_CLEAN = """
    from horovod_tpu.common import faults
    def f():
        if faults.ACTIVE:
            faults.inject("tcp.recv", rank=0, peer=1)
"""

HVD003_SUPPRESSED = """
    from horovod_tpu.common import faults
    def f():
        faults.inject("tcp.rcv")  # hvdlint: disable=HVD003 -- fixture: deliberately bogus site
"""


def test_hvd003_registry_is_populated():
    # The rule is only as good as the registry parse; guard it.
    assert "tcp.recv" in PROJECT.fault_sites
    assert len(PROJECT.fault_sites) >= 6


def test_hvd003_unknown_site():
    vs = run(HVD003_VIOLATING)
    assert codes(vs) == ["HVD003"]
    assert "tcp.rcv" in vs[0].message


def test_hvd003_known_site():
    assert run(HVD003_CLEAN) == []


def test_hvd003_suppressed():
    assert run(HVD003_SUPPRESSED) == []


def test_hvd003_every_site_documented():
    doc_path = os.path.join(REPO_ROOT, "docs", "fault_injection.md")
    with open(doc_path, encoding="utf-8") as f:
        doc = f.read()
    for site in PROJECT.fault_sites:
        assert f"`{site}`" in doc, (
            f"fault site {site!r} missing from docs/fault_injection.md")


# ---------------------------------------------------------------------------
# HVD004 — swallowed exception in thread bodies
# ---------------------------------------------------------------------------

HVD004_VIOLATING = """
    import threading
    def _worker_loop():
        while True:
            try:
                step()
            except Exception:
                pass
    threading.Thread(target=_worker_loop, name="w", daemon=True).start()
"""

HVD004_CLEAN = """
    import threading
    def _worker_loop():
        while True:
            try:
                step()
            except Exception as e:
                log.error("worker died: %s", e)
    def _other_loop():
        try:
            step()
        except ValueError:
            pass  # narrow type: fine
    def not_a_thread_body():
        try:
            step()
        except Exception:
            pass  # broad, but not a thread body: HVD004 does not apply
"""

HVD004_SUPPRESSED = """
    def _worker_loop():
        try:
            step()
        except Exception:  # hvdlint: disable=HVD004 -- fixture: probe loop, errors expected
            pass
"""


def test_hvd004_violating():
    vs = run(HVD004_VIOLATING)
    assert codes(vs) == ["HVD004"]
    assert "_worker_loop" in vs[0].message


def test_hvd004_clean():
    assert run(HVD004_CLEAN) == []


def test_hvd004_base_exception():
    # BaseException is broader than Exception — the one-word change that
    # would reopen the silent-loop-death class must not lint clean.
    src = """
        import threading
        def _worker_loop():
            try:
                step()
            except BaseException:
                pass
        threading.Thread(target=_worker_loop, name="w").start()
    """
    assert codes(run(src)) == ["HVD004"]


def test_hvd004_suppressed():
    assert run(HVD004_SUPPRESSED) == []


def test_hvd004_thread_subclass_run():
    src = """
        import threading
        class Pump(threading.Thread):
            def __init__(self):
                super().__init__(name="pump")
            def run(self):
                try:
                    go()
                except Exception:
                    pass
    """
    assert codes(run(src)) == ["HVD004"]


def test_hvd004_stash_and_surface_is_loud():
    # Capturing the exception object for the parent to surface (error
    # list, attribute) is propagation, not a silent swallow.
    src = """
        import threading
        errs = []
        def _worker_loop():
            try:
                step()
            except BaseException as e:
                errs.append(e)
        threading.Thread(target=_worker_loop, name="w").start()
    """
    assert run(src) == []


# ---------------------------------------------------------------------------
# HVD005 — wire-tag invariants (scoped to core/messages.py)
# ---------------------------------------------------------------------------

MESSAGES_PATH = os.path.join(PKG, "core", "messages.py")

HVD005_DUPLICATE = """
    A_MAGIC = 0x11111111
    B_MAGIC = 0x11111111
    class F:
        def to_bytes(self):
            w = Writer()
            w.u32(A_MAGIC)
            return w.getvalue()
    class G:
        def to_bytes(self):
            w = Writer()
            w.u32(B_MAGIC)
            return w.getvalue()
"""

HVD005_MISSING_MAGIC = """
    A_MAGIC = 0x11111111
    class F:
        def to_bytes(self):
            w = Writer()
            w.u8(1)
            return w.getvalue()
"""

HVD005_MAGIC_NOT_FIRST = """
    A_MAGIC = 0x11111111
    class F:
        def to_bytes(self):
            w = Writer()
            w.u8(2)
            w.u32(A_MAGIC)
            return w.getvalue()
"""

HVD005_CTRL_BIT = """
    A_MAGIC = 0x11111111
    FLAG = 1 << 63
    class F:
        def to_bytes(self):
            w = Writer()
            w.u32(A_MAGIC)
            return w.getvalue()
"""

HVD005_CLEAN = """
    A_MAGIC = 0x11111111
    B_MAGIC = 0x22222222
    class F:
        def to_bytes(self):
            w = Writer()
            w.u32(A_MAGIC)
            return w.getvalue()
"""


def test_hvd005_duplicate_magic():
    vs = run(HVD005_DUPLICATE, path=MESSAGES_PATH)
    assert codes(vs) == ["HVD005"]
    assert "duplicates" in vs[0].message


def test_hvd005_missing_magic():
    vs = run(HVD005_MISSING_MAGIC, path=MESSAGES_PATH)
    assert codes(vs) == ["HVD005"]
    assert "to_bytes" in vs[0].message


def test_hvd005_magic_not_first_write():
    # A u8 written before the u32 magic shifts the leading bytes off the
    # tag even though a magic u32 exists somewhere in to_bytes.
    vs = run(HVD005_MAGIC_NOT_FIRST, path=MESSAGES_PATH)
    assert codes(vs) == ["HVD005"]
    assert "first field" in vs[0].message


def test_hvd005_ctrl_bit():
    # The top-bit literal violates both the messages-layer contract
    # (HVD005: don't touch the transport's control bit) and the registry
    # split (HVD008: bit 56-63 literals live in frame_bits.py only).
    vs = run(HVD005_CTRL_BIT, path=MESSAGES_PATH)
    assert codes(vs) == ["HVD005", "HVD008"]
    assert "control-frame" in next(
        v.message for v in vs if v.code == "HVD005")


def test_hvd005_clean_and_scoped():
    assert run(HVD005_CLEAN, path=MESSAGES_PATH) == []
    # The same duplicate-magic source outside core/messages.py is not
    # this rule's business.
    assert run(HVD005_DUPLICATE) == []


# -- extended header layout (integrity plane): frame_bits.py contract --

FRAME_BITS_PATH = os.path.join(PKG, "transport", "frame_bits.py")

HVD005_BITS_CLEAN = """
    import struct
    _LEN = struct.Struct("<Q")
    _CRC = struct.Struct("<I")
    _CTRL_FLAG = 1 << 63
    _DEFER_FLAG = 1 << 62
    _DIGEST_FLAG = 1 << 61
"""

HVD005_BITS_WRONG_LEN = """
    import struct
    _LEN = struct.Struct("<I")
    _CRC = struct.Struct("<I")
    _CTRL_FLAG = 1 << 63
    _DEFER_FLAG = 1 << 62
    _DIGEST_FLAG = 1 << 61
"""

HVD005_BITS_NO_CRC = """
    import struct
    _LEN = struct.Struct("<Q")
    _CTRL_FLAG = 1 << 63
    _DEFER_FLAG = 1 << 62
    _DIGEST_FLAG = 1 << 61
"""

HVD005_BITS_NO_CTRL = """
    import struct
    _LEN = struct.Struct("<Q")
    _CRC = struct.Struct("<I")
    _DEFER_FLAG = 1 << 62
    _DIGEST_FLAG = 1 << 61
"""

HVD005_BITS_WRONG_DEFER = """
    import struct
    _LEN = struct.Struct("<Q")
    _CRC = struct.Struct("<I")
    _CTRL_FLAG = 1 << 63
    _DEFER_FLAG = 1 << 60
    _DIGEST_FLAG = 1 << 61
"""

HVD005_MESSAGES_CRC = """
    import zlib
    A_MAGIC = 0x11111111
    class F:
        def to_bytes(self):
            w = Writer()
            w.u32(A_MAGIC)
            w.u32(zlib.crc32(bytes(w.buf)))
            return w.getvalue()
"""


def test_hvd005_transport_header_clean():
    assert run(HVD005_BITS_CLEAN, path=FRAME_BITS_PATH) == []
    # The bit-56..63 literals are RESERVED for frame_bits.py — owning
    # them there is the contract, not a violation (HVD008 is scoped out).


def test_hvd005_transport_wrong_len_format():
    vs = run(HVD005_BITS_WRONG_LEN, path=FRAME_BITS_PATH)
    assert codes(vs) == ["HVD005"]
    assert "_LEN" in vs[0].message and "'<Q'" in vs[0].message


def test_hvd005_transport_missing_crc_struct():
    vs = run(HVD005_BITS_NO_CRC, path=FRAME_BITS_PATH)
    assert codes(vs) == ["HVD005"]
    assert "_CRC" in vs[0].message


def test_hvd005_transport_missing_ctrl_flag():
    vs = run(HVD005_BITS_NO_CTRL, path=FRAME_BITS_PATH)
    assert codes(vs) == ["HVD005"]
    assert "_CTRL_FLAG" in vs[0].message


def test_hvd005_transport_flag_on_wrong_bit():
    # A flag declared on the WRONG bit is the same contract break as a
    # missing one: the reservation names a position, not just a name.
    vs = run(HVD005_BITS_WRONG_DEFER, path=FRAME_BITS_PATH)
    assert codes(vs) == ["HVD005"]
    assert "_DEFER_FLAG" in vs[0].message


def test_hvd005_real_frame_bits_passes():
    vs = lint_paths([os.path.join(PKG, "transport", "frame_bits.py")],
                    PROJECT)
    assert vs == [], vs


def test_hvd005_messages_must_not_crc():
    # The CRC envelope is the transport's; a second checksum computed in
    # messages.py would drift from it (two integrity layers, no owner).
    vs = run(HVD005_MESSAGES_CRC, path=MESSAGES_PATH)
    assert codes(vs) == ["HVD005"]
    assert "crc" in vs[0].message.lower()
    # ...and crc32 outside the scoped files is not this rule's business.
    assert run(HVD005_MESSAGES_CRC) == []


# ---------------------------------------------------------------------------
# HVD006 — anonymous threads
# ---------------------------------------------------------------------------

HVD006_VIOLATING = """
    import threading
    threading.Thread(target=print, daemon=True).start()
"""

HVD006_CLEAN = """
    import threading
    threading.Thread(target=print, name="printer", daemon=True).start()
"""

HVD006_SUPPRESSED = """
    import threading
    threading.Thread(target=print, daemon=True).start()  # hvdlint: disable=HVD006 -- fixture: throwaway
"""

HVD006_SUBCLASS_VIOLATING = """
    import threading
    class Pump(threading.Thread):
        def __init__(self, stream):
            super().__init__(daemon=True)
            self._stream = stream
"""

HVD006_SUBCLASS_CLEAN = """
    import threading
    class Pump(threading.Thread):
        def __init__(self, stream, name):
            super().__init__(daemon=True, name=name)
            self._stream = stream
    class Pump2(threading.Thread):
        def __init__(self):
            super().__init__(daemon=True)
            self.name = "pump2"
"""


def test_hvd006_violating():
    assert codes(run(HVD006_VIOLATING)) == ["HVD006"]


def test_hvd006_clean():
    assert run(HVD006_CLEAN) == []


def test_hvd006_suppressed():
    assert run(HVD006_SUPPRESSED) == []


def test_hvd006_thread_subclass():
    # Subclass instantiation has no target= kw, so the Thread(...) check
    # never fires — the subclass __init__ itself must name the thread.
    vs = run(HVD006_SUBCLASS_VIOLATING)
    assert codes(vs) == ["HVD006"]
    assert "Pump" in vs[0].message
    assert run(HVD006_SUBCLASS_CLEAN) == []


def test_hvd006_executor_needs_name_prefix():
    src = """
        from concurrent.futures import ThreadPoolExecutor
        pool = ThreadPoolExecutor(max_workers=2)
    """
    assert codes(run(src)) == ["HVD006"]


# ---------------------------------------------------------------------------
# HVD007 — metric names must come from (and be documented in) the catalog
# ---------------------------------------------------------------------------

HVD007_VIOLATING = """
    from horovod_tpu.core import metrics
    def f():
        metrics.inc("nonexistent_metric_total")
"""

HVD007_CLEAN = """
    from horovod_tpu.core import metrics
    from horovod_tpu.core.timeline import phase_stats, wire_stats
    def f(dt):
        metrics.inc("faults_injected_total")
        metrics.set_gauge("tensor_queue_depth", 3)
        metrics.observe("collective_latency_seconds", dt, op="ALLREDUCE")
        wire_stats.add("bytes_on_wire", 128)
        phase_stats.add("negotiate", dt)
        unrelated.observe("whatever")  # not the metrics receiver
"""

HVD007_SUPPRESSED = """
    from horovod_tpu.core import metrics
    def f():
        metrics.inc("nonexistent_metric_total")  # hvdlint: disable=HVD007 -- fixture: testing the suppression path
"""


def test_hvd007_catalog_is_populated():
    names = PROJECT.metric_catalog
    assert "collective_latency_seconds" in names
    assert "bytes_on_wire" in names     # wire_stats literal
    assert "negotiate" in names         # phase_stats literal


def test_hvd007_unknown_metric():
    vs = run(HVD007_VIOLATING)
    assert codes(vs) == ["HVD007"]
    assert "nonexistent_metric_total" in vs[0].message


def test_hvd007_stats_add_checked_too():
    vs = run("""
        from horovod_tpu.core.timeline import wire_stats
        def f():
            wire_stats.add("bytes_on_wrie", 4)
    """)
    assert codes(vs) == ["HVD007"]


@pytest.mark.parametrize("call", [
    'phase("udpate")', 'timeline_mod.phase("udpate", step=3)', 'phase(name)'])
def test_hvd007_phase_literal_checked_too(call):
    vs = run(f"""
        from horovod_tpu.core import timeline as timeline_mod
        from horovod_tpu.core.timeline import phase
        def f(name):
            with {call}:
                pass
            with phase("update", step=1), timeline_mod.phase("wait"):
                pass
            unrelated.phase("whatever")  # not the timeline's
    """)
    assert codes(vs) == ["HVD007"]


def test_hvd007_computed_name_rejected():
    vs = run("""
        from horovod_tpu.core import metrics
        def f(name):
            metrics.inc(name)
    """)
    assert codes(vs) == ["HVD007"]
    assert "literal" in vs[0].message


def test_hvd007_clean():
    assert run(HVD007_CLEAN) == []


def test_hvd007_suppressed():
    assert run(HVD007_SUPPRESSED) == []


def test_hvd007_every_metric_documented():
    """The real registry file must pass (every CATALOG entry backticked
    in docs/observability.md) — the HVD003 doc-mirror contract, metrics
    flavor.  Checked via the real file so a catalog addition without its
    doc row fails here by name."""
    path = os.path.join(PKG, "core", "metrics.py")
    vs = lint_paths([path], PROJECT)
    assert [v for v in vs if v.code == "HVD007"] == [], vs


def test_hvd007_undocumented_metric_detected(tmp_path):
    """A catalog entry absent from the doc must be flagged — proven with
    a shadow project root whose doc is empty-ish but whose registry has
    one extra name."""
    shadow = tmp_path / "root"
    (shadow / "horovod_tpu" / "core").mkdir(parents=True)
    (shadow / "docs").mkdir()
    (shadow / "horovod_tpu" / "core" / "metrics.py").write_text(
        'CATALOG = {"documented_total": ("counter", "x"),\n'
        '           "undocumented_total": ("counter", "y")}\n')
    (shadow / "docs" / "observability.md").write_text(
        "only `documented_total` appears here\n")
    vs = lint_paths([str(shadow / "horovod_tpu" / "core" / "metrics.py")],
                    Project(root=str(shadow)))
    assert codes(vs) == ["HVD007"]
    assert "undocumented_total" in vs[0].message


# ---------------------------------------------------------------------------
# HVD008 — frame-header bit literals live only in transport/frame_bits.py
# ---------------------------------------------------------------------------

HVD008_VIOLATING = """
    MY_CTRL = 1 << 63
"""

HVD008_DTYPE_LANE = """
    def stamp(code):
        return code << 56
"""

HVD008_REBIND = """
    import struct
    _CTRL_FLAG = 1 << 40
"""

HVD008_WIRE_CODE_REBIND = """
    _WIRE_DTYPE_INT8 = 3
"""

HVD008_WIRE_CODE_CLEAN = """
    from horovod_tpu.transport.frame_bits import (_WIRE_DTYPE_INT8,
                                                  _WIRE_DTYPE_ONEBIT,
                                                  _WIRE_DTYPE_TOPK)
    def codec_code(name):
        return {"int8": _WIRE_DTYPE_INT8, "onebit": _WIRE_DTYPE_ONEBIT,
                "topk": _WIRE_DTYPE_TOPK}[name]
"""

HVD008_CLEAN = """
    from horovod_tpu.transport.frame_bits import _CTRL_FLAG, _FLAGS_MASK
    def is_ctrl(word):
        return bool(word & _CTRL_FLAG)
    LOW_BIT = 1 << 8          # below the flag lane: not wire framing
    WIDE = (1 << 64) - 1      # a width mask, not a lane position
"""

HVD008_SUPPRESSED = """
    MY_CTRL = 1 << 63  # hvdlint: disable=HVD008 -- fixture: testing the suppression path
"""


def test_hvd008_bit_literal():
    vs = run(HVD008_VIOLATING)
    assert codes(vs) == ["HVD008"]
    assert "frame_bits" in vs[0].message


def test_hvd008_dtype_lane_literal():
    # Re-deriving the dtype lane shift (bit 56) is the same fork as the
    # flag bits, even when the left operand is a variable.
    vs = run(HVD008_DTYPE_LANE)
    assert codes(vs) == ["HVD008"]


def test_hvd008_registry_name_rebind():
    # Shadowing a registry name forks the contract even with an
    # off-lane value.
    vs = run(HVD008_REBIND)
    assert codes(vs) == ["HVD008"]
    assert "_CTRL_FLAG" in vs[0].message


def test_hvd008_wire_dtype_code_rebind():
    # Re-defining a wire-dtype CODE outside frame_bits.py forks the
    # compression skew contract — two peers could stamp the same lane
    # value for different codecs and mis-decode instead of aborting.
    vs = run(HVD008_WIRE_CODE_REBIND)
    assert codes(vs) == ["HVD008"]
    assert "_WIRE_DTYPE_INT8" in vs[0].message


def test_hvd008_wire_dtype_code_import_is_clean():
    assert run(HVD008_WIRE_CODE_CLEAN) == []


def test_hvd008_clean():
    assert run(HVD008_CLEAN) == []


def test_hvd008_suppressed():
    assert run(HVD008_SUPPRESSED) == []


def test_hvd008_scoped_out_of_frame_bits():
    # The registry itself is the one place the literals belong (the
    # fixture still trips HVD005's header-contract check there, which is
    # that rule's business, not this one's).
    vs = run(HVD008_VIOLATING, path=FRAME_BITS_PATH)
    assert [v for v in vs if v.code == "HVD008"] == []


# ---------------------------------------------------------------------------
# HVD009 — shm control words move only through the accessor helpers
# ---------------------------------------------------------------------------

SHM_PATH = os.path.join(PKG, "transport", "shm.py")

HVD009_VIOLATING = """
    import struct
    _U64 = struct.Struct("<Q")
    _OFF_L2H_HEAD = 256
    def peek_head(buf):
        return _U64.unpack_from(buf, _OFF_L2H_HEAD)[0]
"""

HVD009_ATTR_VIOLATING = """
    import struct
    _U32 = struct.Struct("<I")
    def peek_bell(buf, p):
        return _U32.unpack_from(buf, p.in_data_bell_off)[0]
"""

HVD009_CLEAN = """
    import struct
    _HDR = struct.Struct("<II")
    def walk(blob, off):
        return _HDR.unpack_from(blob, off)
"""

HVD009_SUPPRESSED = """
    import struct
    _U64 = struct.Struct("<Q")
    _OFF_L2H_HEAD = 256
    def peek_head(buf):
        return _U64.unpack_from(buf, _OFF_L2H_HEAD)[0]  # hvdlint: disable=HVD009 -- fixture: testing the suppression path
"""

HVD009_SHM_ACCESSOR_CLEAN = """
    import struct
    _U64 = struct.Struct("<Q")
    def _load_u64(buf, off):
        return _U64.unpack_from(buf, off)[0]
    def _store_u64(buf, off, value):
        _U64.pack_into(buf, off, value)
"""

HVD009_SHM_BARE_STRUCT = """
    import struct
    _HDR = struct.Struct("<II")
    def sidestep(buf, off):
        return _HDR.unpack_from(buf, off)
"""


def test_hvd009_offset_constant():
    vs = run(HVD009_VIOLATING)
    assert codes(vs) == ["HVD009"]
    assert "_OFF_L2H_HEAD" in vs[0].message


def test_hvd009_offset_attribute():
    vs = run(HVD009_ATTR_VIOLATING)
    assert codes(vs) == ["HVD009"]
    assert "in_data_bell_off" in vs[0].message


def test_hvd009_clean_bare_offset_elsewhere():
    # journal.py-style framed walks over a blob use plain offsets; only
    # the shm header-offset vocabulary marks a control word.
    assert run(HVD009_CLEAN) == []


def test_hvd009_suppressed():
    assert run(HVD009_SUPPRESSED) == []


def test_hvd009_shm_accessors_are_the_allowlist():
    # Inside transport/shm.py the four accessors may move raw structs...
    assert run(HVD009_SHM_ACCESSOR_CLEAN, path=SHM_PATH) == []
    # ...and ANY other struct move in that file is a hole in the
    # model-checked access set, offset vocabulary or not.
    vs = run(HVD009_SHM_BARE_STRUCT, path=SHM_PATH)
    assert codes(vs) == ["HVD009"]
    assert "accessors" in vs[0].message


# ---------------------------------------------------------------------------
# HVD000 — suppression hygiene
# ---------------------------------------------------------------------------

def test_suppression_requires_justification():
    src = """
        import threading, time
        lock = threading.Lock()
        def f():
            with lock:
                time.sleep(1)  # hvdlint: disable=HVD001
    """
    vs = run(src)
    # The unjustified suppression is itself a violation AND does not
    # silence the original finding.
    assert codes(vs) == ["HVD000", "HVD001"]
    assert "justification" in next(
        v.message for v in vs if v.code == "HVD000")


def test_suppression_unknown_code_is_error():
    src = 'x = 1  # hvdlint: disable=HVD999 -- bogus\n'
    vs = run(src)
    assert codes(vs) == ["HVD000"]
    assert "HVD999" in vs[0].message


def test_suppression_on_preceding_comment_line():
    src = """
        import threading, time
        lock = threading.Lock()
        def f():
            with lock:
                # hvdlint: disable=HVD001 -- fixture: applies to next line
                time.sleep(1)
    """
    assert run(src) == []


# ---------------------------------------------------------------------------
# the tree-wide contract
# ---------------------------------------------------------------------------

def test_tree_is_clean(tree_violations):
    assert tree_violations == [], "\n".join(
        f"{v.path}:{v.line}: {v.code} {v.message}" for v in tree_violations)


def test_no_anonymous_threads_in_tree(tree_violations):
    # Satellite contract: lockdep and the stall inspector must be able to
    # attribute every background thread by name.
    assert [v for v in tree_violations if v.code == "HVD006"] == []


# ---------------------------------------------------------------------------
# HVD010 — rendezvous scope names come from transport/scopes.py
# ---------------------------------------------------------------------------

SCOPES_PATH = os.path.join(PKG, "transport", "scopes.py")

HVD010_VIOLATING = """
    def renew(store, identity, payload):
        store.set("lease", identity, payload)
"""

HVD010_BATCH_VIOLATING = """
    def publish(store, identity, blob):
        store.batch([("set", "rank_and_size", identity, blob)])
"""

HVD010_REBIND = """
    LEASE_SCOPE = "lease"
"""

HVD010_CLEAN = """
    from horovod_tpu.transport.scopes import LEASE_SCOPE
    def renew(store, identity, payload):
        store.set(LEASE_SCOPE, identity, payload)
    def local_lookup(fetched):
        return fetched.get("epoch_ack")      # dict key, not a wire scope
    def own_namespace(store, key):
        return store.get("myapp_private", key)   # unregistered scope
"""

HVD010_SUPPRESSED = """
    def renew(store, identity, payload):
        store.set("lease", identity, payload)  # hvdlint: disable=HVD010 -- fixture: testing the suppression path
"""


def test_hvd010_call_literal():
    vs = run(HVD010_VIOLATING)
    assert codes(vs) == ["HVD010"]
    assert "scopes.py" in vs[0].message


def test_hvd010_batch_tuple_literal():
    vs = run(HVD010_BATCH_VIOLATING)
    assert codes(vs) == ["HVD010"]
    assert "rank_and_size" in vs[0].message


def test_hvd010_registry_name_rebind():
    vs = run(HVD010_REBIND)
    assert codes(vs) == ["HVD010"]
    assert "LEASE_SCOPE" in vs[0].message


def test_hvd010_clean():
    assert run(HVD010_CLEAN) == []


def test_hvd010_suppressed():
    assert run(HVD010_SUPPRESSED) == []


def test_hvd010_scoped_out_of_scopes_registry():
    # The registry file itself is where the literals belong.
    vs = run(HVD010_REBIND, path=SCOPES_PATH)
    assert [v for v in vs if v.code == "HVD010"] == []


def test_hvd010_registry_parsed_not_imported():
    # The project parses scope VALUES out of transport/scopes.py's AST;
    # the wire names the control plane depends on must all be there.
    scopes = set(PROJECT.scope_registry)
    assert {"lease", "rank_and_size", "epoch_ack", "reset_request",
            "demotion_report", "driver", "metrics"} <= scopes


@pytest.mark.parametrize("code,fixture", [
    ("HVD001", HVD001_WITH),
    ("HVD002", HVD002_VIOLATING),
    ("HVD003", HVD003_VIOLATING),
    ("HVD004", HVD004_VIOLATING),
    ("HVD006", HVD006_VIOLATING),
    ("HVD007", HVD007_VIOLATING),
    ("HVD008", HVD008_VIOLATING),
    ("HVD009", HVD009_VIOLATING),
    ("HVD010", HVD010_VIOLATING),
])
def test_seeded_violation_fails_with_right_code(tmp_path, code, fixture):
    """Seeding any single violation into a linted tree must fail the pass
    with exactly that rule code (the acceptance-criteria probe)."""
    seeded = tmp_path / "seeded.py"
    seeded.write_text(textwrap.dedent(fixture))
    vs = lint_paths([str(tmp_path)], PROJECT)
    assert codes(vs) == [code]


def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(HVD006_VIOLATING))
    assert main([str(tmp_path), "--root", REPO_ROOT]) == 1
    out = capsys.readouterr().out
    assert "HVD006" in out
    good = tmp_path / "sub"
    good.mkdir()
    (good / "ok.py").write_text("x = 1\n")
    assert main([str(good), "--root", REPO_ROOT]) == 0


def test_rule_codes_catalog():
    assert RULE_CODES == {"HVD000", "HVD001", "HVD002", "HVD003",
                          "HVD004", "HVD005", "HVD006", "HVD007",
                          "HVD008", "HVD009", "HVD010"}
