"""Chip benchmark of the serving stack: cells, traffic, readers, reference.

Everything the benchmark needs to judge a run lives here and is found by
name: ``cells/<cell>.json``, ``configs/<config>.json``,
``traffic/<mix>.json`` and ``metrics/<metric>.py``. ``run.py`` is the
entry point.
"""
