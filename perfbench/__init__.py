"""The benchmark of this repository: see ``perfbench/run.py`` and PERF.md."""
