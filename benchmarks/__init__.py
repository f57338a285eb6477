"""Benchmark harness: one module per paper table/figure.

| module            | paper anchor                                   |
|-------------------|------------------------------------------------|
| codecs            | Tables 5.4 / 5.5 (codec ratio + C/D speed)     |
| frontier_stats    | Fig 5.2 / Table 5.3 (frontier distribution)    |
| bfs_comm          | Tables 7.4 / 7.5 (per-zone volume + time)      |
| scaling           | Fig 7.1 / 7.2 (strong / weak scaling)          |
| breakdown         | Fig 7.3 (per-zone time breakdown)              |
| teps              | §2.6.3 (TEPS, 64-root harmonic mean)           |

``python -m benchmarks.run`` executes reduced-size versions of all of them
(scaling via ``--full``: its grids run on sub-meshes of the process's devices).
"""
