import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST run before any other import: jax locks the device count on first
# init, and the production meshes below need 512 host placeholder devices.

"""Multi-pod dry-run: ``.lower().compile()`` every (architecture x input
shape) cell on the single-pod (16, 16) mesh and the 2-pod (2, 16, 16) mesh,
then record memory_analysis / cost_analysis / collective traffic per cell.

    PYTHONPATH=src python -m repro.launch.dryrun                  # everything
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b  # one arch
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multipod  # 512 chips
    PYTHONPATH=src python -m repro.launch.dryrun --force          # recompile

Results are cached per-cell as JSON under results/dryrun/<mesh>/ so the full
sweep is resumable; EXPERIMENTS.md §Dry-run and the roofline table read them.
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.launch import hlo_analysis, hlo_cost
from repro.launch.mesh import HW, make_production_mesh
from repro.launch.steps import build_cell, model_flops

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun")


def _get(d: dict, *names, default=0.0):
    for n in names:
        if n in d:
            return d[n]
    return default


def _analytic_state_bytes(cell) -> int:
    """Exact per-device bytes of the cell's persistent arguments (params,
    optimizer state, KV cache) from their NamedShardings — the
    hardware-honest HBM floor.  CPU `memory_analysis` additionally carries
    f32 copies of every bf16 dot operand (the CPU backend has no bf16
    matmul), which a TPU executable does not."""
    total = 0
    args_flat = jax.tree.leaves(
        cell.args, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
    )
    sh_flat = jax.tree.leaves(
        cell.in_shardings,
        is_leaf=lambda x: hasattr(x, "shard_shape"),
    )
    for a, sh in zip(args_flat, sh_flat):
        if not isinstance(a, jax.ShapeDtypeStruct) or a.ndim == 0:
            continue
        try:
            local = sh.shard_shape(a.shape)
        except Exception:
            local = a.shape
        n = 1
        for d in local:
            n *= d
        total += n * a.dtype.itemsize
    return total


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str) -> dict:
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh)
    fn = jax.jit(
        cell.fn,
        in_shardings=cell.in_shardings,
        out_shardings=cell.out_shardings,
        donate_argnums=cell.donate_argnums,
    )
    with jax.set_mesh(mesh):   # activates the P()-based constraints
        lowered = fn.lower(*cell.args)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, (list, tuple)):
        xla_cost = xla_cost[0]
    # Trip-count-aware analysis (XLA's cost_analysis counts while bodies
    # once — useless for scan-over-layers programs; see hlo_cost.py).
    cost = hlo_cost.analyze(compiled.as_text())

    chips = mesh.devices.size
    flops_per_chip = float(cost["flops"])
    bytes_per_chip = float(cost["bytes"])
    coll_per_chip = float(cost["coll_bytes"])
    stats_by_op = cost["coll_by_op"]
    pod_fraction = 0.0
    if "pod" in mesh.axis_names:
        # conservatively assume gradients/activations crossing pods are the
        # all-reduce share (pure DP on the pod axis)
        ar = stats_by_op.get("all-reduce", 0)
        pod_fraction = 0.0 if coll_per_chip == 0 else min(
            1.0, 0.5 * ar / coll_per_chip
        )
    rl = hlo_analysis.roofline(
        flops_per_chip, bytes_per_chip, coll_per_chip, HW,
        pod_fraction=pod_fraction,
    )
    mflops = model_flops(arch, shape_name)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": int(chips),
        "status": "ok",
        "compile_s": round(t_compile, 1),
        "kind": cell.meta.get("kind"),
        "meta": cell.meta,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
            ),
            "fits_16gb": bool(
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < HW["hbm_per_chip"]
            ),
            "analytic_state_bytes": _analytic_state_bytes(cell),
        },
        "cost": {
            "flops_per_chip": flops_per_chip,
            "bytes_per_chip": bytes_per_chip,
            "collective_bytes_per_chip": coll_per_chip,
            "collectives": stats_by_op,
            "collective_counts": cost["coll_counts"],
            "xla_cost_analysis_flops": float(_get(xla_cost, "flops")),
        },
        "roofline": rl,
        "model_flops_total": mflops,
        "model_flops_per_chip": mflops / chips,
        "useful_flops_ratio": (
            mflops / chips / flops_per_chip if flops_per_chip else 0.0
        ),
    }
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multipod", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=RESULTS)
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multipod", "both"):
        meshes.append(("multipod_2x16x16", make_production_mesh(multi_pod=True)))

    failures = 0
    for mesh_name, mesh in meshes:
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch, shape_name, ok, why in configs.all_cells():
            if args.arch and arch != args.arch:
                continue
            if args.shape and shape_name != args.shape:
                continue
            path = os.path.join(outdir, f"{arch}__{shape_name}.json")
            if not ok:
                rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                       "status": "skipped", "reason": why}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"[{mesh_name}] {arch:28s} {shape_name:12s} SKIP ({why[:60]})")
                continue
            if os.path.exists(path) and not args.force:
                with open(path) as f:
                    prev = json.load(f)
                if prev.get("status") == "ok":
                    print(f"[{mesh_name}] {arch:28s} {shape_name:12s} cached")
                    continue
            try:
                rec = run_cell(arch, shape_name, mesh, mesh_name)
                rl = rec["roofline"]
                print(
                    f"[{mesh_name}] {arch:28s} {shape_name:12s} OK "
                    f"compile={rec['compile_s']:7.1f}s "
                    f"peak={rec['memory']['peak_bytes']/1e9:6.2f}GB "
                    f"dominant={rl['dominant']:10s} "
                    f"bound={rl['step_time_lower_bound_s']:.3e}s",
                    flush=True,
                )
            except Exception as e:  # a failing cell is a bug: record + count
                failures += 1
                rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                       "status": "error", "error": str(e)[:2000],
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"[{mesh_name}] {arch:28s} {shape_name:12s} "
                      f"FAIL {str(e)[:120]}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
