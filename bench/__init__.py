"""The chip benchmark: one command (``bench/run.py``) that serves a cell's
traffic through the engine, times it, checks what it served against a
plain float32 reference and prints one JSON line.  Everything that
belongs to one configuration, model family, traffic mix, cell or
per-layer metric is a file of its own, found by name (``configs/``,
``families/`` by a configuration's ``model_type``, ``traffic/``,
``workloads/``, ``metrics/``)."""
