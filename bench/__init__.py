"""The on-chip benchmark of the Graph500 BFS path: ``BENCHMARK.json`` names
its cells, and ``bench/run.py`` runs one of them.  Everything the yardstick
needs lives here: the Kronecker generator, root sampling, the TEPS count,
the plain reference BFS, the trace reduction and the per-layer readers."""
