"""Chip benchmark of the serving stack: cells, traffic, readers, blocks.

Everything the benchmark needs to judge a run lives here and is found by
name: ``cells/<cell>.json``, ``configs/<config>.json``,
``traffic/<mix>.json``, ``metrics/<metric>.py`` and the block a
configuration names, ``blocks/<block>.py`` (its reference and counts).
``run.py`` is the entry point.
"""
