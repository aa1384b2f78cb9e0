"""On-chip benchmark of the resident serving path (see PERF.md).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Configurations,
traffic mixes and metrics are files found by the names the benchmark
file gives them: ``configs/<config>.{json,py}``, ``traffic/<mix>.json``
(with ``cells/<cell>.json`` for a cell's own parameters) and
``metrics/<metric>.py``.
"""
