"""The benchmark of proteinbert_tpu: harness, yardstick and data files.

`python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of BENCHMARK.json once. Everything that belongs to one
configuration, traffic mix, cell or per-layer metric is a file of its own,
found by the name BENCHMARK.json gives it (see PERF.md, section 3).
"""
