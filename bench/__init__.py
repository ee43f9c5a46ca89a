"""On-chip benchmark of the federated training path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything a cell needs is found by
name: its configuration under ``bench/configs/``, its traffic mix under
``bench/traffic/``, its limits under ``bench/cells/`` and each per-layer
metric's reader under ``bench/metrics/``.
"""
