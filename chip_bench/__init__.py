"""The chip benchmark: ``hvdrun`` -> ``hvd.init()`` -> training steps on a TPU.

``python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` and prints the contract's
result line last.  Everything that belongs to one configuration, one traffic
mix or one per-layer metric is a file under ``configs/``, ``traffic/`` or
``metrics/``, found by the name ``BENCHMARK.json`` gives it; the modules
beside this file are the general harness.  ``PERF.md`` says what each cell
and metric is for.
"""
