"""Chip benchmark of the SLaB serving engine.

``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator it
finds and prints one JSON result line. Everything a cell needs is data
found by name: a configuration file under ``configs/``, a traffic mix
under ``mixes/``, a correctness limit under ``limits/``, and one reader
per per-layer metric under ``metrics/``.
"""
