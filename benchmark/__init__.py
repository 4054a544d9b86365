"""The benchmark of cells: one runner (``run.py``), and everything that
belongs to one model, configuration, traffic mix or per-layer metric as
files of its own (``models/``, ``configs/``, ``traffic/``, ``metrics/``)."""
