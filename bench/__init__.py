"""Chip benchmark of conv training on the tap-GEMM kernels.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything a cell needs is found by
name: its configuration under ``bench/configs``, its traffic under
``bench/traffic``, its limits under ``bench/checks``, its step kind under
``bench/steps`` and each per-layer metric's reader under ``bench/metrics``.
"""
