"""The eight per-layer metrics of ISSUE 52: data files over
``delta_per_step`` that read what a reduced tensor waits for from
``phase_stats`` (``negotiate_wait``, ``negotiate_recv``, the rounds, the
runtime threads' CPU).  Each file is held to the table of the issue, read on
made-up counters to a value worked out by hand, and reduced over made-up
ranks; nothing here runs a worker.
"""

import pytest

from chip_bench import readers, spec

E1, E4 = "resnet50-eager-1chip", "resnet50-eager-4chip"
NEGOTIATION, BUILDERS = "negotiation and fusion", "step builders"
# name: (counters, ranks, cells, layer, source, unit)
TABLE = {
    "negotiate_wait_ms_step": (
        ["phase_ms.negotiate_wait"], "max", [E1, E4], NEGOTIATION,
        "program_span", "ms/step"),
    "negotiate_wait_rank0_ms_step": (
        ["phase_ms.negotiate_wait"], "rank0", [E4], NEGOTIATION,
        "program_span", "ms/step"),
    "negotiate_recv_ms_step": (
        ["phase_ms.negotiate_recv"], "max", [E4], NEGOTIATION,
        "program_span", "ms/step"),
    "negotiate_recv_rank0_ms_step": (
        ["phase_ms.negotiate_recv"], "rank0", [E4], NEGOTIATION,
        "program_span", "ms/step"),
    "negotiate_rounds_step": (
        ["phase_count.negotiate", "phase_count.negotiate_idle"], "max",
        [E1, E4], NEGOTIATION, "program_counter", "count/step"),
    "runtime_cpu_ms_step": (
        ["phase_ms.cpu.loop", "phase_ms.cpu.dispatch"], "max", [E1, E4],
        NEGOTIATION, "program_counter", "ms/step"),
    "runtime_cpu_rank0_ms_step": (
        ["phase_ms.cpu.loop", "phase_ms.cpu.dispatch"], "rank0", [E4],
        NEGOTIATION, "program_counter", "ms/step"),
    "update_cpu_ms_step": (
        ["phase_ms.cpu.update"], "max", [E1, E4], BUILDERS,
        "program_counter", "ms/step"),
}
NAMES = sorted(TABLE)

# What four ranks counted over a window of 10 steps: rank 0 announces last
# (it waits 2 ms a step where the others wait 30), its receives find every
# frame there, its runtime threads run longest.
STEPS = 10
RANKS = [
    {"phase_ms.negotiate_wait": 20.0, "phase_ms.negotiate_recv": 4.0,
     "phase_count.negotiate": 15, "phase_count.negotiate_idle": 695,
     "phase_ms.negotiate": 21.0, "phase_ms.negotiate_idle": 400.0,
     "phase_ms.cpu.loop": 150.0, "phase_ms.cpu.dispatch": 30.0,
     "phase_ms.cpu.update": 90.0, "phase_count.cpu.loop": 710},
    {"phase_ms.negotiate_wait": 300.0, "phase_ms.negotiate_recv": 250.0,
     "phase_count.negotiate": 16, "phase_count.negotiate_idle": 696,
     "phase_ms.negotiate": 22.0, "phase_ms.negotiate_idle": 500.0,
     "phase_ms.cpu.loop": 100.0, "phase_ms.cpu.dispatch": 20.0,
     "phase_ms.cpu.update": 110.0, "phase_count.cpu.loop": 712},
    {"phase_ms.negotiate_wait": 310.0, "phase_ms.negotiate_recv": 240.0,
     "phase_count.negotiate": 15, "phase_count.negotiate_idle": 694,
     "phase_ms.negotiate": 20.0, "phase_ms.negotiate_idle": 510.0,
     "phase_ms.cpu.loop": 90.0, "phase_ms.cpu.dispatch": 25.0,
     "phase_ms.cpu.update": 100.0, "phase_count.cpu.loop": 709},
    {"phase_ms.negotiate_wait": 290.0, "phase_ms.negotiate_recv": 260.0,
     "phase_count.negotiate": 15, "phase_count.negotiate_idle": 695,
     "phase_ms.negotiate": 19.0, "phase_ms.negotiate_idle": 505.0,
     "phase_ms.cpu.loop": 95.0, "phase_ms.cpu.dispatch": 22.0,
     "phase_ms.cpu.update": 105.0, "phase_count.cpu.loop": 710},
]
# By hand: rank 0's value and the largest, a step.
BY_HAND = {
    "negotiate_wait_ms_step": 31.0,             # rank 2: 310 / 10
    "negotiate_wait_rank0_ms_step": 2.0,        # 20 / 10
    "negotiate_recv_ms_step": 26.0,             # rank 3: 260 / 10
    "negotiate_recv_rank0_ms_step": 0.4,        # 4 / 10
    "negotiate_rounds_step": 71.2,              # rank 1: (16 + 696) / 10
    "runtime_cpu_ms_step": 18.0,                # rank 0: (150 + 30) / 10
    "runtime_cpu_rank0_ms_step": 18.0,
    "update_cpu_ms_step": 11.0,                 # rank 1: 110 / 10
}


def _ctx(deltas, world=4):
    return {"fields": {}, "deltas": deltas, "steps": STEPS, "window": None,
            "world": world, "flops_per_step": 0.0, "peak_flops": 1.0}


def _file(name):
    return spec.Cell(E4).reader(name)


def _entry(name):
    found = [m for m in spec.benchmark()["per_layer"] if m["name"] == name]
    assert len(found) == 1
    return found[0]


@pytest.mark.parametrize("name", NAMES)
def test_file_is_one_delta_per_step_over_the_issues_counters(name):
    counters, ranks = TABLE[name][:2]
    metric = _file(name)
    assert metric["name"] == name
    assert metric["readers"] == [{"reduction": "delta_per_step",
                                  "counters": counters}]
    assert metric["ranks"] == ranks
    assert "delta_per_step" in readers.REDUCTIONS
    # The sentence a reader of the result line gets: what it counts, and
    # for the pairs which rank.
    assert len(metric["what"]) > 80
    if ranks == "rank0":
        assert "rank 0" in metric["what"]


@pytest.mark.parametrize("name", NAMES)
def test_every_counter_is_a_phase_of_the_program(name):
    from horovod_tpu.core.timeline import PHASES

    for counter in TABLE[name][0]:
        kind, _, phase = counter.partition(".")
        assert kind in ("phase_ms", "phase_count"), counter
        assert phase in PHASES, counter
        # worker.py::_counters exports exactly these two a phase.
        assert "*" not in counter and "?" not in counter


@pytest.mark.parametrize("name", NAMES)
def test_entry_is_the_row_of_the_issues_table(name):
    _, _, cells, layer, source, unit = TABLE[name]
    assert _entry(name) == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "samples_per_s_chip", "workloads": cells}


@pytest.mark.parametrize("name", NAMES)
def test_entry_lists_only_eager_cells_and_each_cell_finds_it(name):
    bench = spec.benchmark()
    traffic = {w["name"]: w["traffic"] for w in bench["workloads"]}
    cells = _entry(name)["workloads"]
    assert cells and all(traffic[c].startswith("eager") for c in cells)
    for cell in traffic:
        listed = name in [m["name"] for m in spec.Cell(cell).per_layer]
        assert listed == (cell in cells)


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_worked_out_value_through_the_readers(name):
    metric = _file(name)
    values = [readers.read(metric, _ctx(deltas)) for deltas in RANKS]
    counters = TABLE[name][0]
    assert values == [pytest.approx(sum(d[c] for c in counters) / STEPS)
                      for d in RANKS]
    assert readers.across_ranks(metric, values) == pytest.approx(
        BY_HAND[name])


@pytest.mark.parametrize("name", NAMES)
def test_rank0_files_take_rank_0_and_max_files_the_largest(name):
    metric = _file(name)
    values = [readers.read(metric, _ctx(deltas)) for deltas in RANKS]
    got = readers.across_ranks(metric, values)
    if TABLE[name][1] == "rank0":
        assert got == values[0]
        # ... whatever the others read, and also where it is the smallest.
        assert readers.across_ranks(metric, [values[0], 1e9]) == values[0]
    else:
        assert got == max(values)
        assert readers.across_ranks(metric, values[::-1]) == got


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counters_reads_nothing_and_does_not_raise(
        name):
    # The parent commit has negotiate's count and none of the new names.
    parent = {"phase_ms.negotiate": 21.0, "phase_ms.wait": 340.0,
              "phase_ms.update": 500.0}
    metric = _file(name)
    assert readers.read(metric, _ctx(parent)) is None
    assert readers.across_ranks(metric, [None] * 4) is None
    assert readers.read(metric, {**_ctx(RANKS[0]), "steps": 0}) is None


def test_the_pairs_differ_only_in_their_rule():
    pairs = [("negotiate_wait_ms_step", "negotiate_wait_rank0_ms_step"),
             ("negotiate_recv_ms_step", "negotiate_recv_rank0_ms_step"),
             ("runtime_cpu_ms_step", "runtime_cpu_rank0_ms_step")]
    for widest, rank0 in pairs:
        assert _file(widest)["readers"] == _file(rank0)["readers"]
        assert (_file(widest)["ranks"], _file(rank0)["ranks"]) \
            == ("max", "rank0")
        assert _entry(rank0)["workloads"] == [E4]


def test_a_tensors_way_adds_up_from_the_files():
    # queue_wait + negotiate_wait + fusion_host + collective_dispatch is
    # what the issue holds against update_wait: every phase between add
    # and the callback is in one of the four files.
    chain = {"queue_wait", "negotiate_wait", "dispatch_wait", "fuse",
             "collective", "unfuse"}
    read = set()
    for name in ("queue_wait_ms_step", "negotiate_wait_ms_step",
                 "fusion_host_ms_step", "collective_dispatch_ms_step"):
        for reader in _file(name)["readers"]:
            read |= {c.partition(".")[2] for c in reader["counters"]}
    assert chain <= read


def test_benchmark_holds_the_eight_entries_in_the_issues_order():
    names = [m["name"] for m in spec.benchmark()["per_layer"]]
    first = names.index("negotiate_wait_ms_step")
    # Behind everything PR 51 had, the first of them at place 40.
    assert first == 40
    assert names[first:first + 8] == [
        "negotiate_wait_ms_step", "negotiate_wait_rank0_ms_step",
        "negotiate_recv_ms_step", "negotiate_recv_rank0_ms_step",
        "negotiate_rounds_step", "runtime_cpu_ms_step",
        "runtime_cpu_rank0_ms_step", "update_cpu_ms_step"]
