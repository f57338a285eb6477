"""Device time of the wire plans (device scopes ``bfs/transpose``,
``column``, ``row``, ``row-pull``, ``unreached``, ``termination``,
``degree``, ``values``: each exchange's collectives and its compaction and packing,
butterfly stages included) over device busy time, as a mean over the
cell's chips (profiler trace; each op counts in its innermost phase,
``bench.phases``).  A program without the scopes gives nothing."""

from bench import phases


def read(reading):
    return phases.share(reading, phases.is_wire)
