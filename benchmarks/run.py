"""Run every benchmark at reduced size; one CSV block per paper table.

    PYTHONPATH=src python -m benchmarks.run [--full] [--skip scaling]

Also writes ``BENCH_comm.json`` (per-zone / per-format communication bytes
from the CommStats host replay) so successive PRs have a machine-readable
perf trajectory to compare against.
"""

from __future__ import annotations

import argparse
import json
import os
import time
import traceback


def write_bench_comm(
    path: str,
    full: bool,
    table: list[dict] | None = None,
    policy_levels: dict | None = None,
    batch: dict | None = None,
    teps: dict | None = None,
) -> None:
    from benchmarks import bfs_comm

    from repro.core import csr as csrmod

    scale, rows, cols = _bench_comm_size(full)
    # the padding rule partition_2d applies (1024-multiple chunks): the
    # staged-byte-model check recomputes wire geometry from (n, chunk)
    n, chunk = csrmod.padded_geometry(1 << scale, rows, cols)
    prebuilt = None
    if table is None or batch is None:
        # one graph + hub reference for both replay suites
        prebuilt = bfs_comm.build_replay_graph(scale, rows, cols)
    if table is None:
        table, policy_levels = bfs_comm.run(
            scale=scale, rows=rows, cols=cols, prebuilt=prebuilt
        )
    if batch is None:
        batch = bfs_comm.run_batch(
            scale=scale, rows=rows, cols=cols, prebuilt=prebuilt
        )
    # the multi-source rows ride the same table (batch column + per-source
    # bytes); single-source rows carry batch=1 for uniform consumers
    for r in table:
        r.setdefault("batch", 1)
    for policy, entry in batch["policies"].items():
        for plan, d in entry["plans"].items():
            table.append(
                {
                    "policy": policy,
                    "zone": "total",
                    "format": "packed",
                    "plan": plan,
                    "batch": d["batch"],
                    "bytes": d["total_bytes"],
                    "bytes_per_source": d["bytes_per_source"],
                    "b1_total_bytes": d["b1_total_bytes"],
                }
            )
    doc = {
        "benchmark": "bfs_comm",
        "scale": scale,
        "rows": rows,
        "cols": cols,
        "chunk": chunk,  # the staged byte model needs s and n
        "n": n,
        "policies": list(bfs_comm.POLICIES),
        "plans": list(bfs_comm.PLANS),
        "table": table,
        # per-policy per-level direction + packed row bytes: makes the
        # direction-opt vs top_down wire saving visible level by level
        "policy_levels": policy_levels or {},
        # multi-source batch section: B=4 planes vs the B=1 replay of the
        # same packed-wire model (shared headers + consensus amortization)
        "batch": batch,
        # Graph500 Kernel-2 throughput: harmonic-mean TEPS over the spec's
        # valid-root sample (benchmarks.teps), the trajectory's speed row
        "teps": teps or {},
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# wrote {path} ({len(table)} rows)")


def _bench_comm_size(full: bool) -> tuple[int, int, int]:
    return (17, 4, 4) if full else (15, 2, 2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-closer sizes (slow)")
    ap.add_argument("--skip", nargs="*", default=[])
    ap.add_argument(
        "--bench-json",
        default=os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                             "BENCH_comm.json"),
        help="where to write the BENCH_comm.json trajectory artifact",
    )
    args = ap.parse_args()
    if args.full and "scaling" not in args.skip:
        from benchmarks import scaling

        # the scaling grids run on sub-meshes of one process's devices; on
        # the CPU that needs forced host devices before JAX starts
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={scaling.DEVICES}",
        )

    from benchmarks import bfs_comm, codecs, frontier_stats, teps

    bench_table: list[tuple] = []  # shared with write_bench_comm below
    teps_box: list[dict] = []  # harmonic-TEPS row, shared the same way

    def teps_suite() -> None:
        teps_box.append(teps.main())

    def bfs_comm_suite() -> None:
        scale, rows, cols = _bench_comm_size(args.full)
        # one graph + hub reference for both replay suites
        prebuilt = bfs_comm.build_replay_graph(scale, rows, cols)
        table, policy_levels = bfs_comm.run(
            scale=scale, rows=rows, cols=cols, prebuilt=prebuilt
        )
        bfs_comm.print_table(table)
        bfs_comm.print_levels(policy_levels)
        batch = bfs_comm.run_batch(
            scale=scale, rows=rows, cols=cols, prebuilt=prebuilt
        )
        bfs_comm.print_batch(batch)
        bench_table.append((table, policy_levels, batch))

    suites = [
        ("codecs (Tables 5.4/5.5)", codecs.main),
        ("frontier_stats (Fig 5.2 / Table 5.3)", frontier_stats.main),
        ("bfs_comm (Tables 7.4/7.5)", bfs_comm_suite),
        ("teps (§2.6.3)", teps_suite),
    ]
    if args.full and "scaling" not in args.skip:
        suites.append(("scaling (Fig 7.1/7.2)", scaling.main))

    failures = []
    for name, fn in suites:
        key = name.split(" ")[0]
        if key in args.skip:
            continue
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            fn()
            print(f"# done in {time.time() - t0:.1f}s")
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    # the artifact reuses the suite's table; a skipped or failed bfs_comm
    # must not be silently re-run here
    if "bench-json" not in args.skip and bench_table:
        try:
            table, policy_levels, batch = bench_table[0]
            write_bench_comm(
                args.bench_json, args.full, table=table,
                policy_levels=policy_levels, batch=batch,
                teps=teps_box[0] if teps_box else None,
            )
        except Exception:  # noqa: BLE001
            failures.append("bench-json")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nALL BENCHMARKS OK")


if __name__ == "__main__":
    main()
