"""perfbench — the benchmark: one command, cells and metrics as data.

``python -m perfbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once in one process
on the chip it is started on.  See ``PERF.md`` for what is measured.
"""
