"""Host seconds of the program's graph construction and placement:
``build_csr``, ``partition_2d``, and ``build_bfs`` with ``shard_blocked``
(host clock, the benchmark's spans around the calls)."""


def read(reading):
    return reading.spans.total("kernel1.csr", "kernel1.partition", "kernel1.placement")
