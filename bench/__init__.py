"""The chip benchmark of the data-VCS engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Everything a cell needs is found
by name: its configuration in ``bench/configs/``, its traffic mix in
``bench/traffic/``, and each per-layer metric's reader in ``bench/metrics/``.
"""
