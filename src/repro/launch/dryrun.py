import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run driver (deliverable e).  The two lines above MUST
# precede every other import — jax locks the device count on first init.
#
# For every (architecture x input-shape x mesh[ x variant]) cell:
#   jit(step, in_shardings, out_shardings).lower(*abstract_args).compile()
# then records memory_analysis(), cost_analysis() and the collective
# schedule into EXPERIMENTS/dryrun/<cell>.json for the roofline tables.
#
# Usage:
#   python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
#   python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
#   python -m repro.launch.dryrun --arch dlrm-rm2 --shape train_batch \
#       --variant row_tables

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import zstandard         # noqa: E402

from repro import configs                      # noqa: E402
from repro.launch import compile_cache, roofline, steps  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

# The production mesh is compiled for, not run on: roofline terms use
# the published peaks of the chip it models.
TARGET_DEVICE_KIND = "TPU v5 lite"
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "EXPERIMENTS", "dryrun")


def cell_path(arch, shape, mesh_name, variant):
    return os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh_name}__{variant}.json")


def hlo_path(arch, shape, mesh_name, variant):
    return os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh_name}__{variant}.hlo.zst")


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             variant: str = "baseline", verbose: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    cell = steps.build_cell(arch, shape, mesh, multi_pod=multi_pod,
                            variant=variant)
    record = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "variant": variant,
        "n_chips": int(n_chips), "kind": cell.kind,
    }
    if cell.skip:
        record["status"] = "skipped"
        record["skip_reason"] = cell.skip
        return record
    try:
        with mesh:
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             out_shardings=cell.out_shardings,
                             donate_argnums=cell.donate)
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            hlo_text = compiled.as_text()
            with open(hlo_path(arch, shape, mesh_name, variant), "wb") as f:
                f.write(zstandard.ZstdCompressor(level=6).compress(
                    hlo_text.encode()))
            analysis = roofline.analyze(compiled, hlo_text,
                                        cell.model_flops_per_step, n_chips,
                                        TARGET_DEVICE_KIND)
        record.update(status="ok", lower_s=round(t_lower, 2),
                      compile_s=round(t_compile, 2), analysis=analysis)
        if verbose:
            mem = analysis["memory_analysis"]
            print(f"[{arch} x {shape} x {mesh_name} x {variant}] OK  "
                  f"flops/chip={analysis['hlo_flops_per_chip']:.3e}  "
                  f"bytes/chip={analysis['hlo_bytes_per_chip']:.3e}  "
                  f"coll/chip={analysis['collective_bytes_per_chip']:.3e}  "
                  f"dominant={analysis['dominant']}  "
                  f"roofline={analysis['roofline_fraction']:.3f}")
            print(f"  memory_analysis: {mem}")
            print(f"  cost_analysis: flops={analysis['hlo_flops_per_chip']:.4e} "
                  f"bytes={analysis['hlo_bytes_per_chip']:.4e}")
    except Exception as e:
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[{arch} x {shape} x {mesh_name} x {variant}] "
                  f"FAILED: {e}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--include-colbert", action="store_true")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute roofline terms from saved HLO")
    args = ap.parse_args()
    compile_cache.enable()

    os.makedirs(OUT_DIR, exist_ok=True)
    targets: list[tuple[str, str]] = []
    if args.all:
        archs = list(configs.ASSIGNED)
        if args.include_colbert:
            archs.append("colbert")
        for a in archs:
            for s in configs.get(a).shapes:
                targets.append((a, s))
    else:
        if not args.arch:
            ap.error("--arch required unless --all")
        entry = configs.get(args.arch)
        shapes = [args.shape] if args.shape else list(entry.shapes)
        targets = [(args.arch, s) for s in shapes]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for multi_pod in meshes:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        for arch, shape in targets:
            path = cell_path(arch, shape, mesh_name, args.variant)
            if args.reanalyze:
                hp = hlo_path(arch, shape, mesh_name, args.variant)
                if not (os.path.exists(hp) and os.path.exists(path)):
                    continue
                with open(path) as f:
                    rec = json.load(f)
                text = zstandard.ZstdDecompressor().decompress(
                    open(hp, "rb").read()).decode()
                cell = steps.build_cell(
                    arch, shape, make_production_mesh(multi_pod=multi_pod),
                    multi_pod=multi_pod, variant=args.variant)
                parsed = roofline.parse_hlo_costs(text)
                terms = roofline.roofline_terms(
                    parsed["flops"], parsed["hbm_bytes"],
                    parsed["collective_bytes"], TARGET_DEVICE_KIND)
                rec["analysis"].update(
                    hlo_flops_per_chip=parsed["flops"],
                    hlo_bytes_per_chip=parsed["hbm_bytes"],
                    collective_bytes_per_chip=parsed["collective_bytes"],
                    collective_breakdown=parsed["collective_breakdown"],
                    collective_counts=parsed["collective_counts"],
                    useful_compute_fraction=(
                        cell.model_flops_per_step /
                        (parsed["flops"] * rec["n_chips"])
                        if parsed["flops"] else 0.0),
                    **terms)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"[reanalyze] {arch} x {shape} x {mesh_name}: "
                      f"dominant={terms['dominant']} "
                      f"roofline={terms['roofline_fraction']:.3f}")
                continue
            if args.skip_done and os.path.exists(path):
                try:
                    with open(path) as f:
                        prev = json.load(f)
                except Exception:
                    prev = {}
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[{arch} x {shape} x {mesh_name}] cached, skipping")
                    continue
            rec = run_cell(arch, shape, multi_pod=multi_pod,
                           variant=args.variant)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec["status"] == "error":
                failures += 1
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
