"""The benchmark of nanofed-tpu: one command runs one cell (see ``run.py``).

Everything that belongs to one configuration, one traffic mix, one per-layer metric
or one model family is a file of its own, found by the name ``BENCHMARK.json`` gives:
``configs/<configuration>.json``, ``traffic/<mix>.json``, ``loops/<loop>.py``,
``layer_metrics/<metric>.py``, ``flops/<family>.py``, ``reference/<family>.py``.
"""
