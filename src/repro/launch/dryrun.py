import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above run before ANY other import — jax locks the device
count on first init. 512 placeholder host devices back both production
meshes: (16,16) single pod and (2,16,16) multi-pod.

Per cell: jit(step).lower(abstract args).compile() must succeed;
memory_analysis() proves fit, cost_analysis() + the HLO collective parser
feed §Roofline. Results land in results/dryrun/<cell>.json.

Usage:
    python -m repro.launch.dryrun --arch llama31-8b --shape decode_32k
    python -m repro.launch.dryrun --all [--multi-pod] [--quant int8]
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from repro.configs import ALL_ARCHS, SHAPES_BY_NAME, get_config  # noqa: E402
from repro.launch.hlo_analysis import collective_bytes  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import compute_roofline  # noqa: E402
from repro.launch.specs import build_cell  # noqa: E402
from repro.parallel.sharding import shardctx  # noqa: E402
from repro.quant import BY_NAME as QUANT_BY_NAME  # noqa: E402
from repro.simulate.hardware import HW_BY_NAME  # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             quant: str = "bf16", hw: str = "tpu-v5e",
             rules=None, save: bool = True, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_dev = mesh.devices.size
    qcfg = QUANT_BY_NAME[quant] if quant != "bf16" else None

    t0 = time.time()
    with shardctx(mesh, rules):
        fn, args, in_shardings, donate = build_cell(cfg, shape, mesh, qcfg)
        jf = jax.jit(fn, in_shardings=in_shardings, donate_argnums=donate)
        with mesh:
            lowered = jf.lower(*args)
            compiled = lowered.compile()
    compile_s = time.time() - t0

    cost = compiled.cost_analysis()
    try:
        mem = compiled.memory_analysis()
        mem_d = {k: getattr(mem, k) for k in
                 ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
                 if hasattr(mem, k)}
    except Exception:
        mem_d = {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    terms = compute_roofline(
        cfg, shape, mesh_name=mesh_name, n_devices=n_dev, cost=cost,
        coll_bytes=coll["total"], hw=HW_BY_NAME[hw], quant=quant)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "quant": quant, "hw": hw, "n_devices": n_dev,
        "compile_s": compile_s,
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": {k: int(v) for k, v in mem_d.items()},
        "collective_bytes": coll,
        "roofline": terms.row(),
        "hlo_bytes": len(hlo),
        "status": "ok",
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name} x {quant}] "
              f"compile={compile_s:.1f}s "
              f"flops/dev={terms.flops_per_device:.3g} "
              f"bytes/dev={terms.bytes_per_device:.3g} "
              f"coll/dev={coll['total']:.3g} "
              f"bottleneck={terms.bottleneck} "
              f"frac={terms.roofline_frac:.3f}")
        if mem_d:
            print(f"  memory_analysis: {mem_d}")
    if save:
        RESULTS.mkdir(parents=True, exist_ok=True)
        tag = f"{arch}_{shape_name}_{mesh_name}_{quant}".replace("/", "-")
        (RESULTS / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    return rec


def cells_for(arch: str):
    cfg = get_config(arch)
    return [s.name for s in cfg.shapes()]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quant", default="bf16")
    ap.add_argument("--hw", default="tpu-v5e")
    args = ap.parse_args()

    jobs = []
    archs = ALL_ARCHS if (args.all or not args.arch) else [args.arch]
    for a in archs:
        shapes = cells_for(a) if (args.all or not args.shape) \
            else [args.shape]
        for s in shapes:
            meshes = [args.multi_pod] if not args.both_meshes \
                else [False, True]
            for mp in meshes:
                jobs.append((a, s, mp))

    failures = []
    for a, s, mp in jobs:
        try:
            run_cell(a, s, multi_pod=mp, quant=args.quant, hw=args.hw)
        except Exception as e:
            failures.append((a, s, mp, repr(e)))
            print(f"FAIL [{a} x {s} x mp={mp}]: {e}")
            traceback.print_exc()
    print(f"\n{len(jobs) - len(failures)}/{len(jobs)} cells compiled")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
