"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload g500-s22-1x1.bfs --seed 7 --seconds 51 --trace 0

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers end standard error.  Without a TPU, or with fewer chips
than the cell asks for, it exits 1 and prints no result.  JAX's compile
cache is kept at ``.bench_cache/jax`` in the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    args = parse(argv)
    # the checkout's own compile cache, at a fixed path; the program's
    # enable_compile_cache takes the directory given here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_cache" / "jax")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import spec

    cell = spec.load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, JAX sees {len(devices)}")

    from bench import cell as cellmod
    from repro.launch import runtime

    runtime.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = cellmod.run(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START,
                         log=lambda m: print(f"bench: {m}", file=sys.stderr, flush=True))
    report(result)
    return 0


def report(result: dict) -> None:
    """The compared numbers with their limits as the last lines of
    standard error, and the result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    if str(Path(__file__).resolve().parent) == os.path.abspath(sys.path[0]):
        sys.path.pop(0)  # bench/ itself: its module names must not shadow others
    sys.exit(main())
