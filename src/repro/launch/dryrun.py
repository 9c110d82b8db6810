import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init).  This launcher — and ONLY this launcher — sees 512
# placeholder CPU devices standing in for the production TPU mesh.

"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``
containing ``memory_analysis()`` (proves it fits), ``cost_analysis()``
(FLOPs / bytes for §Roofline) and the per-collective byte totals parsed
from the optimized HLO (the roofline's third term).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
        --shape train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
"""

import argparse
import json
import sys
import time
import traceback


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, algo: str = "dreamddp", verbose: bool = True,
             phase: int | None = None, step_cfg=None,
             variant: str = "", **cell_kw) -> dict:
    import jax

    from ..analysis.hlo import parse_collectives
    from ..configs import SHAPES
    from .cells import build_cell
    from .mesh import make_production_mesh

    mesh_name = "multi_pod" if multi_pod else "single_pod"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with jax.set_mesh(mesh):
        kw = {}
        if SHAPES[shape_name].kind == "train":
            kw = {"algo": algo, "phase": phase, **cell_kw}
            if step_cfg is not None:
                kw["step_cfg"] = step_cfg
        cell = build_cell(arch_id, shape_name, mesh, multi_pod=multi_pod,
                          **kw)
        lowered = cell.lower()
        compiled = lowered.compile()

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem[k] = int(getattr(ma, k, 0) or 0)
        mem["total_bytes"] = (mem.get("argument_size_in_bytes", 0)
                              + mem.get("temp_size_in_bytes", 0)
                              + mem.get("output_size_in_bytes", 0)
                              - mem.get("alias_size_in_bytes", 0))
    except Exception as e:                                   # noqa: BLE001
        mem["error"] = str(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:                                   # noqa: BLE001
        cost["error"] = str(e)

    hlo = compiled.as_text()
    from ..analysis.hlo_costs import parse_module_costs
    executed = parse_module_costs(hlo)       # loop-aware (true trip counts)

    art = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "n_devices": cell.n_devices,
        "model_flops": cell.model_flops,
        "cost_is_per_device": True,
        "memory_analysis": mem,
        # raw XLA numbers (loop bodies counted once) kept for reference
        "cost_analysis_raw": cost,
        # loop-aware executed costs — what §Roofline consumes
        "cost_analysis": {
            "flops": executed.flops,
            "bytes accessed": executed.bytes_accessed,
            "n_dots": executed.n_dots,
            "unknown_loops": executed.unknown_loops,
        },
        "collectives": executed.collectives.to_dict(),
        "collectives_static": parse_collectives(hlo).to_dict(),
        "meta": cell.meta,
        "compile_seconds": time.time() - t0,
    }
    os.makedirs(out_dir, exist_ok=True)
    tag = f"__{variant}" if variant else ""
    path = os.path.join(out_dir,
                        f"{arch_id}__{shape_name}__{mesh_name}{tag}.json")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    import gzip
    with gzip.open(path[:-5] + ".hlo.gz", "wt") as f:
        f.write(hlo)
    if verbose:
        per_dev = mem.get("total_bytes", 0) / 1e9
        print(f"  OK  {arch_id:24s} {shape_name:12s} {mesh_name:10s} "
              f"flops/dev={executed.flops:.3e} "
              f"mem/dev={per_dev:.2f}GB "
              f"wire/dev={executed.collectives.total_wire_bytes / 1e9:.3f}GB "
              f"[{art['compile_seconds']:.0f}s]")
    return art


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--algo", default="dreamddp")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--variant", default="")
    ap.add_argument("--intra-worker", default="tp",
                    choices=("tp", "fsdp", "dp", "ep2"))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    from ..configs import ARCHS, all_cells

    if args.all:
        cells = all_cells()
    else:
        if args.arch is None:
            ap.error("--arch or --all required")
        archs = [args.arch] if args.arch != "all" else list(ARCHS)
        cells = [(a, s.name) for a in archs
                 for s in ARCHS[a].shapes()
                 if args.shape in (None, s.name)]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch_id, shape_name in cells:
        for mp in meshes:
            mesh_name = "multi_pod" if mp else "single_pod"
            path = os.path.join(
                args.out, f"{arch_id}__{shape_name}__{mesh_name}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"  skip {arch_id} {shape_name} {mesh_name}")
                continue
            try:
                run_cell(arch_id, shape_name, multi_pod=mp,
                         out_dir=args.out, algo=args.algo,
                         variant=args.variant,
                         intra_worker=args.intra_worker)
            except Exception:                                # noqa: BLE001
                failures.append((arch_id, shape_name, mesh_name))
                print(f"  FAIL {arch_id} {shape_name} {mesh_name}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} cell(s) FAILED: {failures}")
        return 1
    print("\nall requested cells compiled")
    return 0


if __name__ == "__main__":
    sys.exit(main())
