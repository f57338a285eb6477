"""Device time of the expansion's COO residue (device scopes
``bfs/expand/push/residue`` and ``bfs/expand/pull/residue``: the hub rows
the hybrid split keeps out of the ELL slab, the per-slot frontier-bit
gathers and the first-hit scan) over device busy time, as a mean over the
cell's chips (profiler trace; each op counts in its innermost phase,
``bench.phases``).  A program without the scopes gives nothing."""

from bench import phases


def read(reading):
    return phases.share(reading, phases.is_residue)
