"""The plain reference and the comparison that decides ``correct``.

:class:`Reference` is a straightforward host BFS over the benchmark's own
edge list: the input tuples, symmetrised, sorted by destination, and a
level-synchronous sweep that ORs the frontier bits of every edge's source
into its destination, with one bit per root so that all the roots of a run
share each sweep.  It imports nothing of the program.

:func:`compare` holds each search the window ran to it: the level plane
must equal the reference's exactly, and every parent must be the root for
the root, -1 for an unreached vertex, and otherwise an input neighbour one
level up.  :meth:`Reference.traversed` is the Graph500 TEPS numerator: the
input tuples with both endpoints in the searched component.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: edges per block of the sweep: blocks end on destination boundaries, so
#: each reduces on its own, and blocks run in threads (numpy's gathers and
#: reductions release the interpreter lock)
BLOCK_EDGES = 1 << 22


class Reference:
    def __init__(self, edges: np.ndarray, n: int, threads: int = 8):
        self.n = n
        self.threads = threads
        u = edges[:, 0].astype(np.int64)
        v = edges[:, 1].astype(np.int64)
        #: input tuples by first endpoint: the TEPS numerator of a component
        #: is the sum over its vertices (an input tuple never leaves one)
        self.tuples_from = np.bincount(u, minlength=n)
        loop = u == v
        keys = np.concatenate([(v << 32) | u, (u << 32) | v])
        keys = keys[~np.concatenate([loop, loop])]
        keys.sort()
        #: symmetric edges sorted by (destination, source), as one key each
        self.keys = keys
        self.src = (keys & 0xFFFFFFFF).astype(np.int32)
        dst = (keys >> 32).astype(np.int32)
        self.start = np.searchsorted(dst, np.arange(n + 1, dtype=np.int32))
        blocks = np.searchsorted(self.start, np.arange(0, keys.size, BLOCK_EDGES))
        self.blocks = np.unique(np.concatenate([[0], blocks, [n]]))

    def _sweep(self, frontier: np.ndarray) -> np.ndarray:
        """OR of the frontier words of each vertex's neighbours."""
        out = np.zeros(self.n, frontier.dtype)

        def block(k):
            lo, hi = self.blocks[k], self.blocks[k + 1]
            starts = self.start[lo:hi]
            has = starts < self.start[lo + 1 : hi + 1]
            if not has.any():
                return
            words = frontier[self.src[self.start[lo] : self.start[hi]]]
            first = starts[has] - self.start[lo]
            out[lo:hi][has] = np.bitwise_or.reduceat(words, first)

        with ThreadPoolExecutor(self.threads) as pool:
            list(pool.map(block, range(len(self.blocks) - 1)))
        return out

    def levels(self, roots, max_levels: int | None = None) -> np.ndarray:
        """``(len(roots), n)`` int32 BFS levels, -1 where unreached.
        ``max_levels`` stops the sweep after that many levels (the
        control's early stop); None runs to the end."""
        roots = np.asarray(roots, np.int64)
        out = np.full((roots.size, self.n), -1, np.int32)
        for lo in range(0, roots.size, 64):
            part = roots[lo : lo + 64]
            bit = np.left_shift(np.uint64(1), np.arange(part.size, dtype=np.uint64))
            seen = np.zeros(self.n, np.uint64)
            np.bitwise_or.at(seen, part, bit)
            frontier = seen.copy()
            out[lo + np.arange(part.size), part] = 0
            depth = 0
            while frontier.any() and (max_levels is None or depth < max_levels):
                depth += 1
                frontier = self._sweep(frontier) & ~seen
                seen |= frontier
                at = np.flatnonzero(frontier)
                words = frontier[at]
                for k in range(part.size):
                    hit = ((words >> np.uint64(k)) & np.uint64(1)).astype(bool)
                    out[lo + k, at[hit]] = depth
        return out

    def parents(self, root: int, level: np.ndarray) -> np.ndarray:
        """A BFS parent plane that fits ``level``: each reached vertex's
        smallest neighbour one level up, the root itself, -1 elsewhere."""
        dst = np.repeat(np.arange(self.n, dtype=np.int32), np.diff(self.start))
        up = level[self.src] == level[dst] - 1
        up &= level[dst] > 0
        hit = np.flatnonzero(up)
        v, first = np.unique(dst[hit], return_index=True)
        parent = np.full(self.n, -1, np.int32)
        parent[v] = self.src[hit[first]]
        parent[root] = root
        return parent

    def traversed(self, level: np.ndarray) -> int:
        """Input tuples with both endpoints reached."""
        return int(self.tuples_from[level >= 0].sum())

    def bad_parents(self, root: int, parent: np.ndarray, level: np.ndarray) -> int:
        """Vertices whose parent breaks the BFS-tree rules, against the
        reference ``level`` plane of ``root``."""
        n = self.n
        parent = parent.astype(np.int64)
        bad = int(parent[root] != root)
        bad += int(np.count_nonzero(parent[level < 0] != -1))
        v = np.flatnonzero(level > 0)
        p = parent[v]
        ok = (p >= 0) & (p < n)
        ok[ok] &= level[p[ok]] == level[v[ok]] - 1
        q = (v[ok] << 32) | p[ok]
        at = np.searchsorted(self.keys, q)
        hit = self.keys[np.minimum(at, self.keys.size - 1)] == q
        ok[ok] = hit
        return bad + int(np.count_nonzero(~ok))


@dataclasses.dataclass
class Verdict:
    """What the comparison found over the searches of one run."""

    searches: int = 0
    wrong_searches: int = 0
    wrong_levels: int = 0
    wrong_parents: int = 0
    missing: int = 0
    traversed: list = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return self.searches > 0 and self.wrong_searches == 0 and self.missing == 0

    def numbers(self) -> dict:
        """Each number compared, with its limit (exact comparisons: 0)."""
        return {
            "wrong_levels": {"value": self.wrong_levels, "limit": 0},
            "wrong_parents": {"value": self.wrong_parents, "limit": 0},
            "missing_searches": {"value": self.missing, "limit": 0},
        }


def compare(ref: Reference, roots, answers) -> Verdict:
    """Hold each answer ``(parent, level)`` (host arrays of length n, or
    None for a search that never answered) to the reference."""
    roots = [int(r) for r in roots]
    verdict = Verdict(searches=len(roots))
    distinct = sorted(set(roots))
    truth = dict(zip(distinct, ref.levels(distinct)))
    for root, answer in zip(roots, answers):
        want = truth[root]
        verdict.traversed.append(ref.traversed(want))
        if answer is None:
            verdict.missing += 1
            verdict.wrong_searches += 1
            continue
        parent, level = answer
        lv = int(np.count_nonzero(level != want))
        pa = ref.bad_parents(root, parent, want)
        verdict.wrong_levels += lv
        verdict.wrong_parents += pa
        verdict.wrong_searches += int(lv + pa > 0)
    return verdict
