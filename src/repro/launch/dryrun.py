"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and record memory/cost/collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out artifacts/dryrun

`main` forces 512 host devices (`mesh.force_host_devices`) before anything
starts a jax backend, which locks the device count.
"""
import argparse
import json
import os
import time
import traceback

import jax

from repro.common.config import ASSIGNED_ARCHS, get_config
from repro.launch import hlo_analysis as HA
from repro.launch.mesh import force_host_devices, make_production_mesh
from repro.launch.shapes import SHAPES, adapt_config
from repro.launch.steps import build_case, lower_case


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (train) / 2 N D (inference), N = active params."""
    n = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token


def run_one(arch: str, shape_name: str, mesh_kind: str, **case_kw):
    shape = SHAPES[shape_name]
    base_cfg = get_config(arch)
    if adapt_config(base_cfg, shape) is None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "total_s": 0.0,
                "reason": "pair skipped per DESIGN.md §4 (enc-dec @ 500k)"}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "devices": n_dev}
    t0 = time.time()
    try:
        case = build_case(base_cfg, shape, mesh, **case_kw)
        lowered = lower_case(case)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        analysis = HA.analyze_compiled(compiled)
        rec.update(analysis)
        mf = model_flops(case.cfg, shape)
        rec["model_flops_global"] = mf
        per_dev = analysis["hlo_flops"]
        rec["model_flops_per_device"] = mf / n_dev
        rec["useful_flop_ratio"] = (mf / n_dev) / per_dev if per_dev else 0.0
        rec["status"] = "ok"
        print(compiled.memory_analysis())
        ca = compiled.cost_analysis()
        print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ASSIGNED_ARCHS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches for train shapes")
    ap.add_argument("--tp-inference", action="store_true",
                    help="replicate weights over the data axis for "
                         "prefill/decode (tensor-parallel only, no per-step "
                         "FSDP all-gathers)")
    ap.add_argument("--resume", action="store_true",
                    help="skip pairs whose artifact JSON already has status ok/skipped")
    args = ap.parse_args()
    force_host_devices(512)

    archs = list(ASSIGNED_ARCHS) if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                tag = f"{arch}__{shape}__{mk}"
                path = os.path.join(args.out, tag + ".json")
                if args.resume and os.path.exists(path):
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        results.append(prev)
                        print(f"--- {tag}: cached ({prev['status']})", flush=True)
                        continue
                print(f"=== {tag} ===", flush=True)
                rec = run_one(arch, shape, mk,
                              remat=not args.no_remat,
                              microbatches=args.microbatches,
                              tp_inference=args.tp_inference)
                results.append(rec)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1, default=str)
                status = rec["status"]
                extra = (f" flops/dev={rec.get('hlo_flops', 0):.3e}"
                         f" coll={rec.get('collective_bytes', 0):.3e}B"
                         f" bottleneck={rec.get('bottleneck', '-')}"
                         if status == "ok" else rec.get("error", ""))
                print(f"--- {tag}: {status} ({rec['total_s']}s){extra}",
                      flush=True)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"\n{n_ok} ok, {n_skip} skipped, "
          f"{len(results) - n_ok - n_skip} errors / {len(results)} cases")
    return results


if __name__ == "__main__":
    main()
