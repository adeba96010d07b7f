import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out experiments/dryrun

Each record lands as JSON in <out>/<mesh>/<arch>__<shape>.json; the
roofline benchmark and EXPERIMENTS.md tables read those artifacts.
"""

import argparse
import gc
import json
import time
import traceback

import jax

from repro.configs.registry import all_cells, get_arch
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import model_flops, roofline_terms
from repro.launch.sharding import tree_named_sharding


def _cost_value(cost: dict, key: str) -> float:
    if key in cost:
        return float(cost[key])
    total = 0.0
    for k, v in cost.items():
        if k.startswith(key):
            total += float(v)
    return total


def run_cell(arch_name: str, shape: str, multi_pod: bool, *, verbose: bool = True) -> dict:
    arch = get_arch(arch_name)
    fam = arch.family
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size
    needs_mesh = getattr(fam, "needs_mesh", False)

    t0 = time.perf_counter()
    if needs_mesh:
        state = fam.abstract_state(arch, shape, mesh=mesh)
        inputs = fam.input_specs(arch, shape, mesh=mesh)
        step = fam.step_fn(arch, shape, mesh=mesh)
    else:
        state = fam.abstract_state(arch, shape)
        inputs = fam.input_specs(arch, shape)
        step = fam.step_fn(arch, shape)

    state_ps = fam.state_pspec(arch, shape, mesh)
    input_ps = fam.input_pspec(arch, shape, mesh)
    in_sh = (
        tree_named_sharding(state_ps, mesh),
        tree_named_sharding(input_ps, mesh),
    )

    with jax.set_mesh(mesh):
        if needs_mesh:
            # shard_map fns carry their own specs; in_shardings constrain args.
            lowered = jax.jit(step).lower(state, inputs)
        else:
            lowered = jax.jit(step, in_shardings=in_sh).lower(state, inputs)
        t_lower = time.perf_counter() - t0
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t1

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    # Trip-count-aware analysis (XLA's cost_analysis counts scan bodies
    # once — see hlo_cost.py); xla_* fields keep the raw numbers for
    # comparison.
    hc = analyze_hlo(hlo, n_devices)
    coll = {
        "per_op": hc.per_op_collective,
        "total_bytes": hc.collective_bytes,
        "n_ops": hc.n_collectives,
    }
    flops_dev = hc.flops
    bytes_dev = hc.bytes
    terms = roofline_terms(
        per_device_flops=flops_dev,
        per_device_bytes=bytes_dev,
        per_device_collective_bytes=coll["total_bytes"],
        n_devices=n_devices,
    )
    mf = model_flops(arch, shape)
    # MFU you would achieve if the step ran exactly at its roofline bound:
    # analytic useful flops / (bound time * fleet peak). This is the score
    # the perf loop drives up.
    terms["model_mfu_at_bound"] = (
        mf / (n_devices * 197e12) / terms["step_lower_bound_s"]
        if terms["step_lower_bound_s"]
        else 0.0
    )
    record = {
        "arch": arch_name,
        "shape": shape,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_devices,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "total_per_device": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes,
        },
        "per_device_flops": flops_dev,
        "per_device_bytes": bytes_dev,
        "xla_cost_flops": _cost_value(cost, "flops"),
        "xla_cost_bytes": _cost_value(cost, "bytes accessed"),
        "collectives": coll,
        "roofline": terms,
        "model_flops": mf,
        "useful_flops_ratio": mf / max(1.0, terms["hlo_flops_global"]),
    }
    if verbose:
        mb = record["memory"]["total_per_device"] / 2**20
        print(
            f"[{record['mesh']}] {arch_name}/{shape}: compile {t_compile:.1f}s, "
            f"{mb:.0f} MiB/dev, flops/dev {flops_dev:.3g}, "
            f"coll {coll['total_bytes']/2**20:.1f} MiB/dev, "
            f"bottleneck {terms['bottleneck']} "
            f"({terms['step_lower_bound_s']*1e3:.2f} ms bound)",
            flush=True,
        )
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = (
        all_cells(include_warp=True)
        if args.all
        else [(args.arch, s) for s in (
            [args.shape] if args.shape else get_arch(args.arch).shapes
        )]
    )
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for multi in meshes:
        mesh_name = "multi" if multi else "single"
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch_name, shape in cells:
            path = os.path.join(outdir, f"{arch_name}__{shape}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {mesh_name} {arch_name}/{shape}", flush=True)
                continue
            try:
                rec = run_cell(arch_name, shape, multi)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                rec = {
                    "arch": arch_name,
                    "shape": shape,
                    "mesh": mesh_name,
                    "ok": False,
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:],
                }
                print(f"[FAIL] {mesh_name} {arch_name}/{shape}: {e}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            jax.clear_caches()
            gc.collect()
    print(f"dry-run complete; {failures} failures", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
