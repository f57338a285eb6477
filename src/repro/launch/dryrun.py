import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first (before any jax-importing module):
jax locks the device count at first init, and the dry-run needs 512
placeholder host devices for the (2,16,16) production mesh.  Smoke tests
and benchmarks do NOT import this module and see 1 device.

Per cell this driver records:
  * compile success (the deliverable: sharding coherence on the mesh),
  * ``compiled.memory_analysis()``   — proves the program fits per device,
  * ``compiled.cost_analysis()``     — FLOPs / bytes for §Roofline,
  * parsed collective bytes          — §Roofline's third term,
  * analytic MODEL_FLOPS and the useful-flop ratio.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod-only|--singlepod-only]
    PYTHONPATH=src python -m repro.launch.dryrun --report   # table from JSONs

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import time
import traceback

import jax

from repro.launch import cells as cellslib
from repro.launch import mesh as meshlib
from repro.launch import roofline

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def run_cell(
    arch: str, shape: str, multi_pod: bool, out_dir: str, variant: str = "baseline"
) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "status": "error",
        "variant": variant,
    }
    t0 = time.time()
    try:
        mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
        cell = cellslib.build_cell(arch, shape, mesh, variant=variant)
        if cell.kind == "skip":
            rec.update(status="skip", skip_reason=cell.skip_reason)
            return _write(rec, out_dir)
        rec["meta"] = {
            k: (float(v) if isinstance(v, (int, float)) else v)
            for k, v in cell.meta.items()
        }
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings)
        with jax.set_mesh(mesh):  # bare-PartitionSpec constraints need a mesh
            lowered = jitted.lower(*cell.args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        rec["cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        loop_mult = float(cell.meta.get("loop_mult", 1.0))
        hlo = compiled.as_text()
        terms = roofline.terms_from_compiled(
            compiled,
            chips=mesh.size,
            model_flops=float(cell.meta["model_flops"]),
            loop_mult=loop_mult,
            hlo_text=hlo,
        )
        coll = roofline.parse_collectives(hlo, loop_mult=loop_mult)
        rec["roofline"] = {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "model_flops": terms.model_flops,
            "hlo_flops_scaled": terms.hlo_flops,
            "hlo_bytes_scaled": terms.hlo_bytes,
            "collective_bytes": terms.collective_bytes,
            "collective_breakdown": coll.per_op,
            "useful_flop_ratio": terms.useful_flop_ratio,
            "roofline_fraction": terms.roofline_fraction,
        }
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — per-cell isolation is the point
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return _write(rec, out_dir)


def _write(rec: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if rec.get("variant", "baseline") == "baseline" else f"__{rec['variant']}"
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    status = rec["status"]
    extra = rec.get("skip_reason", rec.get("error", ""))[:90]
    dom = rec.get("roofline", {}).get("dominant", "")
    print(f"[{status:5s}] {rec['arch']:22s} {rec['shape']:14s} {rec['mesh']:8s} "
          f"{rec.get('total_s', 0):7.1f}s {dom:10s} {extra}")
    return rec


def report(out_dir: str) -> None:
    rows = []
    for fn in sorted(os.listdir(out_dir)):
        if fn.endswith(".json"):
            with open(os.path.join(out_dir, fn)) as f:
                rows.append(json.load(f))
    ok = sum(r["status"] == "ok" for r in rows)
    skip = sum(r["status"] == "skip" for r in rows)
    err = sum(r["status"] == "error" for r in rows)
    print(f"cells: {len(rows)}  ok={ok} skip={skip} error={err}")
    for r in rows:
        if r["status"] == "error":
            print(f"  ERROR {r['arch']}/{r['shape']}/{r['mesh']}: {r.get('error')}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    if args.report:
        report(args.out)
        return

    pods = [args.multipod] if not args.both_meshes else [False, True]
    if args.all:
        for arch, shape in cellslib.all_cells():
            for mp in pods:
                run_cell(arch, shape, mp, args.out, variant=args.variant)
        report(args.out)
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    for mp in pods:
        run_cell(args.arch, args.shape, mp, args.out, variant=args.variant)


if __name__ == "__main__":
    main()
