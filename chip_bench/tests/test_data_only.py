"""A configuration, a traffic mix and a per-layer metric are each added as
files: dropped into a copy of the benchmark, they are found by name and run
in rehearsal, and no file that was there is edited (``make_root`` refuses
to overwrite one)."""

import rehearse

from chip_bench import readers, spec

FILES = {
    # A configuration: sizes of its own over the ResNet model file.
    "configs/throwaway-net.json": {
        "module": "resnet50", "stage_sizes": [1, 2, 1, 1], "num_filters": 8,
        "bottleneck_expansion": 4, "num_classes": 7, "image_size": 32,
        "image_channels": 3, "per_chip_batch": 3, "sgd_learning_rate": 0.02,
        "sgd_momentum": 0.5},
    # A traffic mix: another process count and warm-up over a builder the
    # harness has.
    "traffic/throwaway-mix.json": {
        "name": "throwaway-mix", "step_builder": "eager", "processes": 2,
        "warmup_steps": 6, "reference_steps": 2, "traced_steps": 8,
        "steps_in_flight": 2},
    # A per-layer metric over an existing reduction.
    "metrics/throwaway_wait_ms_step.json": {
        "name": "throwaway_wait_ms_step", "ranks": "mean", "readers": [
            {"reduction": "delta_per_step", "counters": ["phase_ms.wait"]}]},
}
ENTRY = {"name": "throwaway_wait_ms_step", "unit": "ms/step",
         "better": "lower", "source": "program_span",
         "layer": "negotiation and fusion", "moves": "samples_per_s_chip",
         "workloads": ["throwaway-cell"]}


def test_new_config_traffic_and_metric_are_files_alone(tmp_path):
    root = rehearse.make_root(
        tmp_path, [("throwaway-cell", "throwaway-net", "throwaway-mix", 4)],
        files=FILES, per_layer=[ENTRY])
    cell = spec.Cell("throwaway-cell", root=root)
    assert cell.sizes["num_classes"] == 7
    assert cell.traffic["processes"] == 2
    assert "throwaway_wait_ms_step" in [m["name"] for m in cell.per_layer]
    # ... and a cell that is there does not report the new metric.
    old = spec.Cell("resnet50-wfbp-1chip", root=root)
    assert "throwaway_wait_ms_step" not in [m["name"] for m in old.per_layer]

    records = rehearse.run_worker(root, "throwaway-cell", 2, trace=1)
    assert len(records) == 2
    for r in records:
        assert all(r["checks"].values()), r["checks"]
        assert r["samples"] == 3 * r["steps"]
        assert len(r["losses"]) == 3            # reference_steps + 1
        assert r["per_layer"]["throwaway_wait_ms_step"] > 0
    value = readers.across_ranks(
        cell.reader("throwaway_wait_ms_step"),
        [r["per_layer"]["throwaway_wait_ms_step"] for r in records])
    assert value == sum(r["per_layer"]["throwaway_wait_ms_step"]
                        for r in records) / 2
