"""The per-layer metrics that read the program's own phases (PR 24): data
files over ``delta_per_step``.  In rehearsal each reads a positive value in
the kind of cell that lists it and is absent from the other kind, and the
two metrics that were there read what they read before."""

import pytest
import rehearse

from chip_bench import spec

EAGER = {
    "update_ms_step": ["phase_ms.update"],
    "update_wait_ms_step": ["phase_ms.wait"],
    "queue_wait_ms_step": ["phase_ms.queue_wait", "phase_ms.dispatch_wait"],
    "fusion_host_ms_step": ["phase_ms.fuse", "phase_ms.unfuse",
                            "phase_ms.tree_unflatten"],
    "optimizer_dispatch_ms_step": ["phase_ms.optimizer_update"],
    "collective_dispatch_ms_step": ["phase_ms.collective"],
    "program_outputs_step": ["phase_count.program_call"],
}
WFBP = {"wfbp_dispatch_ms_step": ["phase_ms.wfbp_dispatch"]}
OLD = {"fuse_unfuse_ms_step": ["phase_ms.fuse", "phase_ms.unfuse"],
       "negotiate_ms_step": ["phase_ms.negotiate"]}
REAL = {"eager": ["resnet50-eager-1chip", "resnet50-eager-4chip"],
        "wfbp": ["resnet50-wfbp-1chip", "bert-large-wfbp-1chip"]}


def _entry(name):
    return [m for m in spec.benchmark()["per_layer"] if m["name"] == name][0]


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    """One traced rehearsal of a tiny eager cell and of a tiny wfbp cell,
    each listed where the real cells of its kind are."""
    tiny = {"eager": "tiny-eager", "wfbp": "tiny-wfbp"}
    per_layer = [{**_entry(name), "workloads": [tiny[kind]]}
                 for kind, names in (("eager", list(EAGER) + list(OLD)),
                                     ("wfbp", list(WFBP)))
                 for name in names]
    root = rehearse.make_root(
        tmp_path_factory.mktemp("spans"),
        [(tiny["eager"], "tiny-resnet", "eager", 1),
         (tiny["wfbp"], "tiny-resnet", "wfbp", 1)], per_layer=per_layer)
    return {kind: rehearse.run_worker(root, cell, 1, trace=1)[0]
            for kind, cell in tiny.items()}


@pytest.mark.parametrize("name", sorted(EAGER) + sorted(WFBP))
def test_new_metric_is_listed_where_the_issue_says(name):
    kind = "eager" if name in EAGER else "wfbp"
    entry = _entry(name)
    assert entry["workloads"] == REAL[kind]
    assert entry["moves"] == "samples_per_s_chip" and entry["better"] == "lower"
    assert entry["source"] == ("program_counter" if "outputs" in name
                               else "program_span")
    for cell in REAL["eager"] + REAL["wfbp"]:
        names = [m["name"] for m in spec.Cell(cell).per_layer]
        assert (name in names) == (cell in REAL[kind])


@pytest.mark.parametrize("name", sorted(EAGER))
def test_eager_metric_reads_its_phases(records, name):
    r = records["eager"]
    expect = sum(r["deltas"][c] for c in EAGER[name]
                 if c in r["deltas"]) / r["steps"]
    assert r["per_layer"][name] == pytest.approx(expect) and expect > 0
    assert name not in records["wfbp"]["per_layer"]
    reader = spec.Cell("resnet50-eager-1chip").reader(name)
    assert reader["readers"] == [{"reduction": "delta_per_step",
                                  "counters": EAGER[name]}]
    assert reader["ranks"] == "max"


def test_wfbp_metric_reads_its_phase(records):
    r = records["wfbp"]
    assert r["per_layer"]["wfbp_dispatch_ms_step"] == pytest.approx(
        r["deltas"]["phase_ms.wfbp_dispatch"] / r["steps"])
    assert r["deltas"]["phase_count.wfbp_dispatch"] == r["steps"]
    assert r["per_layer"]["wfbp_dispatch_ms_step"] > 0
    assert "wfbp_dispatch_ms_step" not in records["eager"]["per_layer"]
    # The one program bypasses the runtime: no other phase moves.
    assert [k for k, v in r["deltas"].items()
            if k.startswith("phase_count.") and v] == [
                "phase_count.wfbp_dispatch"]


def test_counts_per_step_in_the_eager_cell(records):
    d, n = records["eager"]["deltas"], records["eager"]["steps"]
    for phase in ("update", "fuse", "enqueue", "queue_wait", "negotiate",
                  "collective", "unfuse", "wait", "tree_unflatten",
                  "optimizer_update"):
        assert d[f"phase_count.{phase}"] == n, phase
    assert "phase_count.dispatch_wait" not in d          # one rank
    assert d["phase_count.program_call"] % n == 0
    # The parts of update on the calling thread lie inside it.
    parts = sum(d[f"phase_ms.{p}"] for p in (
        "fuse", "enqueue", "wait", "tree_unflatten", "optimizer_update"))
    assert parts <= d["phase_ms.update"] + 0.005 * n


@pytest.mark.parametrize("name", sorted(OLD))
def test_old_metric_reads_the_counters_it_read(records, name):
    reader = spec.Cell("resnet50-eager-1chip").reader(name)
    assert reader["readers"] == [{"reduction": "delta_per_step",
                                  "counters": OLD[name]}]
    r = records["eager"]
    assert r["per_layer"][name] == pytest.approx(
        sum(r["deltas"][c] for c in OLD[name]) / r["steps"])
    assert _entry(name)["workloads"] == REAL["eager"]
