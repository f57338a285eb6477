"""Paper Fig 7.1/7.2: strong and weak scaling of the distributed BFS.

Real multi-rank executions in one process, each grid on a sub-mesh of
``jax.devices()`` (chips on a TPU host; on the CPU, host devices forced
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before JAX
starts, as ``benchmarks.run --full`` does), comparing Baseline (raw) vs
compressed ('auto') — the paper's three-scenario scaling study at reduced
scale.  Reports time per BFS and TEPS.
"""

from __future__ import annotations

import numpy as np

#: host devices the largest grid needs when run on the CPU
DEVICES = 8


def _run_grid(rows: int, cols: int, scale: int, mode: str) -> dict:
    from repro.core import distributed_bfs as dbfs
    from repro.core import validate
    from repro.launch import graph500, runtime

    mesh = runtime.grid_mesh(rows, cols)
    graph = graph500.build_graph(scale, 16, rows, cols, seed=3)
    search = graph500.Search(mesh, graph.bg, dbfs.DistBFSConfig(mode=mode))
    root = int(np.argmax(graph.g.degrees()))
    search(root)  # compile + run
    reps = 3
    times = []
    for _ in range(reps):
        parent, _, depth, dt = search(root)
        times.append(dt)
    search.release()
    dt = sum(times) / reps
    te = validate.traversed_edges(graph.g, parent[0])
    return {"rows": rows, "cols": cols, "scale": scale, "mode": mode,
            "time_s": dt, "teps": te / dt, "depth": depth}


def run(strong_scale: int = 13, weak_base_scale: int = 11) -> list[dict]:
    rows = []
    # strong scaling: fixed problem, growing grid
    for r, c in ((1, 1), (2, 2), (2, 4)):
        for mode in ("raw", "auto"):
            rec = _run_grid(r, c, strong_scale, mode)
            rec["study"] = "strong"
            rows.append(rec)
    # weak scaling: problem grows with the grid (scale+2 per 4x ranks)
    for (r, c), sc in (((1, 1), weak_base_scale), ((2, 2), weak_base_scale + 2)):
        for mode in ("raw", "auto"):
            rec = _run_grid(r, c, sc, mode)
            rec["study"] = "weak"
            rows.append(rec)
    return rows


def main() -> None:
    import jax

    print(f"# device: {len(jax.devices())}x {jax.devices()[0].device_kind}")
    print("study,grid,scale,mode,time_s,TEPS,depth")
    for r in run():
        print(f"{r['study']},{r['rows']}x{r['cols']},{r['scale']},{r['mode']},"
              f"{r['time_s']:.4f},{r['teps']:.3e},{r['depth']}")


if __name__ == "__main__":
    import os

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={DEVICES}"
    )
    main()
