"""Device time of the local expansion (device scopes ``bfs/expand/*``: the
push and pull passes, their ELL slab and COO residue parts, and the masks
and merge around them; the row exchanges inside a pass count as wire)
over device busy time, as a mean over the cell's chips (profiler trace;
each op counts in its innermost phase, ``bench.phases``).  A program
without the scopes gives nothing."""

from bench import phases


def read(reading):
    return phases.share(reading, phases.is_expand)
