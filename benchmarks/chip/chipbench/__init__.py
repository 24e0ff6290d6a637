"""The chip benchmark of the served Verdict query path.

``run.py`` beside this package is the one command; everything a cell needs
is found by name from ``BENCHMARK.json``: a configuration in
``configs/<name>.json``, a traffic mix in ``traffic/<name>.json`` and each
per-layer metric's reader in ``metrics/<name>.py``.
"""
