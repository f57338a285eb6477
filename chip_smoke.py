"""Chip smoke run of the Graph500 kernel-2 BFS path.

This is a smoke run, not a benchmark: it shows that the normal entry
points run on a TPU and give validated trees.  Its timings are printed for
orientation only.  It drives the same steps as
``examples/graph500_benchmark.py`` (:mod:`repro.launch.graph500`): a
scale-22 Kronecker graph at edgefactor 16 (n = 4,194,304), generated from a
seed and partitioned on the host, sampled valid roots, the distributed 2D
driver on a mesh of ``jax.devices()``, and every tree checked with the
Graph500 validation.

    python chip_smoke.py               # one chip, 1x1 grid
    python chip_smoke.py --four-chips  # the 2x2 grid on four chips

One chip runs the harness defaults (``mode=auto``, ``direction_opt``,
``expand=hybrid``, ``bfs``): 8 roots as two B=4 batches plus one B=1 call,
then the same roots with ``expand=coo``.  ``--four-chips`` runs only the
grid path: ``mode=auto`` and ``mode=btfly`` against ``mode=raw`` on the
same two roots (``direction_opt``, ``expand=coo``: expansion is
compute-local, and the grid run is about the wire plans), with the block
placement printed.  The last line of standard
output is ``{"ok": true, "device": {...}}``; when JAX finds no TPU, or any
phase fails, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

SCALE, EDGEFACTOR = 22, 16
ROOTS, BATCH = 8, 4
#: roots of the grid run: one B=2 call per wire plan keeps the four-chip
#: run short (compiles dominate it)
GRID_ROOTS = 2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def peak_memory(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    if None in peaks:
        return "peak device memory not reported"
    return "peak device memory " + ", ".join(f"{p / 2**30:.2f} GiB" for p in peaks)


def setup(rows: int, cols: int, scale: int):
    from repro.launch import graph500

    graph = graph500.build_graph(scale, EDGEFACTOR, rows, cols)
    log(f"set-up (host): generation {graph.gen_s:.1f} s, CSR {graph.csr_s:.1f} s, "
        f"{rows}x{cols} partition {graph.partition_s:.1f} s; n={graph.g.n:,} "
        f"m={graph.g.m:,} e_cap={graph.bg.e_cap:,}")
    return graph


def place(mesh, graph, cfgs: dict):
    """One :class:`repro.launch.graph500.Search` per labelled config, its
    blocks on the mesh."""
    from repro.launch import graph500

    searches = {}
    for label, cfg in cfgs.items():
        t0 = time.perf_counter()
        searches[label] = graph500.Search(mesh, graph.bg, cfg)
        log(f"{label}: blocks built and placed in {time.perf_counter() - t0:.1f} s")
    return searches


def compile_all(jobs, host_work=None):
    """Compile ``(label, search, roots)`` programs concurrently — XLA
    compiles off the interpreter lock, so the programs overlap, and so does
    ``host_work``, whose result is returned."""
    import numpy as np

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(jobs)) as pool:
        futures = [pool.submit(search.compile, roots) for _, search, roots in jobs]
        result = host_work() if host_work else None
        for (label, _, roots), fut in zip(jobs, futures):
            log(f"{label}: compile B={np.size(roots)} {fut.result():.1f} s")
    log(f"all compiles done in {time.perf_counter() - t0:.1f} s")
    return result


def run_batches(graph, search, batches, label: str):
    """Run each batch of roots and validate every tree; returns the
    (B, n) parent and level planes of all batches."""
    import numpy as np

    from repro.launch import graph500

    parents, levels = [], []
    for roots in batches:
        parent, level, depth, dt = search(roots)
        graph500.check_trees(graph.g, roots, parent, level)
        log(f"{label}: B={np.size(roots)} call {dt:.3f} s, depth {depth}, "
            f"{np.size(roots)} tree(s) valid")
        parents.append(parent)
        levels.append(level)
    return np.concatenate(parents), np.concatenate(levels)


def one_chip(mesh, scale: int = SCALE) -> None:
    import numpy as np

    from benchmarks import teps
    from repro.core import distributed_bfs as dbfs
    from repro.core import validate

    graph = setup(1, 1, scale)
    roots = teps.valid_roots(graph.g, ROOTS, seed=2)
    batches = [roots[lo : lo + BATCH] for lo in range(0, ROOTS, BATCH)]
    root = int(roots[0])
    searches = place(mesh, graph, {
        expand: dbfs.DistBFSConfig(mode="auto", policy="direction_opt",
                                   expand=expand, algebra="bfs")
        for expand in ("hybrid", "coo")
    })
    ref = compile_all([("hybrid", searches["hybrid"], batches[0]),
                       ("hybrid", searches["hybrid"], root),
                       ("coo", searches["coo"], batches[0])],
                      host_work=lambda: validate.reference_bfs(graph.g, root))

    _, hybrid = run_batches(graph, searches["hybrid"], batches, "hybrid")
    _, level = run_batches(graph, searches["hybrid"], [root], "hybrid")
    assert np.array_equal(level[0], hybrid[0]), "B=1 levels differ from B=4"
    assert np.array_equal(hybrid[0], ref), "levels differ from reference_bfs"
    log(f"hybrid: levels of root {root} equal reference_bfs")

    _, coo = run_batches(graph, searches["coo"], batches, "coo")
    assert np.array_equal(coo, hybrid), "coo levels differ from hybrid"
    log(f"coo: levels of all {ROOTS} roots equal hybrid; {peak_memory(mesh.devices.flat)}")


def four_chips(mesh, scale: int = SCALE) -> None:
    import numpy as np

    from benchmarks import teps
    from repro.core import distributed_bfs as dbfs
    from repro.core import validate
    from repro.launch import graph500

    graph = setup(2, 2, scale)
    roots = teps.valid_roots(graph.g, GRID_ROOTS, seed=2)
    modes = ("raw", "auto", "btfly")
    searches = place(mesh, graph, {
        mode: dbfs.DistBFSConfig(mode=mode, policy="direction_opt",
                                 expand="coo", algebra="bfs")
        for mode in modes
    })
    placed = {}
    for shard in searches["raw"].blocks[0].addressable_shards:
        block = (shard.index[0].start or 0, shard.index[1].start or 0)
        placed[block] = shard.device
        log(f"grid block {block} on device {shard.device.id} ({shard.device})")
    assert len({d.id for d in placed.values()}) == 4, "blocks share a device"
    refs = compile_all(
        [(mode, searches[mode], roots) for mode in modes],
        host_work=lambda: [validate.reference_bfs(graph.g, int(r)) for r in roots],
    )

    parents, levels = {}, {}
    with ThreadPoolExecutor(1) as pool:
        for mode in modes:
            parents[mode], levels[mode], depth, dt = searches[mode](roots)
            log(f"{mode}: B={len(roots)} call {dt:.3f} s, depth {depth}")
            if mode == "raw":  # the host validates while the chips run on
                checked = pool.submit(graph500.check_trees, graph.g, roots,
                                      parents["raw"], levels["raw"])
        log(peak_memory(mesh.devices.flat))
        checked.result()
    log(f"raw: {len(roots)} trees valid")
    for mode in ("auto", "btfly"):
        assert np.array_equal(levels[mode], levels["raw"]), f"{mode} levels differ from raw"
        if np.array_equal(parents[mode], parents["raw"]):
            log(f"{mode}: parents identical to raw, so the same {len(roots)} valid trees")
        else:
            graph500.check_trees(graph.g, roots, parents[mode], levels[mode])
            log(f"{mode}: {len(roots)} trees valid")
    for k, root in enumerate(roots):
        assert np.array_equal(levels["raw"][k], refs[k]), f"root {root}: not reference_bfs"
    log(f"auto, btfly and raw give identical levels for all {len(roots)} roots, "
        "equal to reference_bfs")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the 2x2 grid path on four chips, and nothing else")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch import runtime

    log(f"smoke run, not a benchmark; compile cache {runtime.enable_compile_cache()}")
    if args.four_chips:
        four_chips(runtime.grid_mesh(2, 2))
    else:
        one_chip(runtime.grid_mesh(1, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
