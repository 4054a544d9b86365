"""The benchmark of cells: one runner (``run.py``), and everything that
belongs to one configuration, traffic mix or per-layer metric as a file
of its own (``configs/``, ``traffic/``, ``metrics/``)."""
