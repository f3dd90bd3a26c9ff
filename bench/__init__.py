"""The chip benchmark: one harness (``run.py``) driven by the data files
under ``configs/``, ``traffic/`` and ``workloads/``."""
