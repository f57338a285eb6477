"""Device time of the row-offset binary searches (``expand.row_offsets``,
device scope ``bfs/row-offsets``: the hybrid residue's in ``local_block``
and the whole block's in the degree vector, both run by every call before
its level loop) over device busy time, as a mean over the cell's chips
(profiler trace; each op counts in its innermost phase, ``bench.phases``).
A program without the scope gives nothing."""

from bench import phases


def read(reading):
    return phases.share(reading, lambda p: p == "bfs/row-offsets")
