"""On-chip benchmark harness: one cell of ``BENCHMARK.json`` per run.

``run.py`` beside this package is the command. Everything that belongs to
one configuration, traffic mix or per-layer metric lives in a file of its
own (``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py``) and is found by the name ``BENCHMARK.json`` gives
it; the app that wires a configuration's pipeline is
``chipbench/apps/<app>.py``, named by the configuration's ``app`` key, with
its plain reference beside it in ``<app>_ref.py``.
"""
