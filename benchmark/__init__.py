"""The benchmark: everything BENCHMARK.json's cells are measured with.

``run.py`` is the one command. Whatever belongs to one configuration, one
traffic mix, one driver, one model family or one per-layer metric sits in a
file of its own that ``run.py`` finds by the name in ``BENCHMARK.json``; a
later PR adds files and entries and edits none that is here (README.md).
"""
