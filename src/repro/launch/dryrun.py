import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
on the production meshes, print memory/cost analysis, and derive roofline
terms.  MUST be run as a module: ``python -m repro.launch.dryrun --arch X
--shape Y [--multipod]`` — the XLA_FLAGS line above runs before any jax
import, giving 512 placeholder host devices.

Outputs one JSON record per combo (optionally appended to --out) consumed by
benchmarks/roofline.py and EXPERIMENTS.md.
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
from jax import set_mesh  # noqa: E402

from ..configs import INPUT_SHAPES, TrainConfig, get_config, list_archs  # noqa: E402
from ..models import model as M  # noqa: E402
from ..models import transformer as tfm  # noqa: E402
from ..sharding import AxisRules  # noqa: E402
from . import hlo_analysis as H  # noqa: E402
from . import hlo_cost  # noqa: E402
from . import steps  # noqa: E402
from .mesh import make_production_mesh, mesh_chips  # noqa: E402


def _with_depth(cfg, n_blocks: int):
    """Same family/dims, reduced to n_blocks scan steps (for per-block cost)."""
    lpb = tfm.layers_per_block(cfg)
    upd = {"num_layers": n_blocks * lpb}
    if cfg.family == "audio":
        upd["encoder_layers"] = max(2, min(cfg.encoder_layers, 2))
    return dataclasses.replace(cfg, **upd)


def _lower_compile(cfg, shape, rules, *, donate=True, tc=None):
    spec = steps.input_specs(cfg, shape, rules, tc)
    step = steps.build_step(cfg, shape, rules, spec)
    jitted = jax.jit(step,
                     in_shardings=spec["in_shardings"],
                     out_shardings=spec["out_shardings"],
                     donate_argnums=spec["donate_argnums"] if donate else ())
    lowered = jitted.lower(*spec["args"])
    compiled = lowered.compile()
    return spec, lowered, compiled


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            seq_parallel: bool = False, verbose: bool = True,
            extra_tags: str = "", cfg=None, tc=None,
            inference_2d: bool = False) -> dict:
    cfg = cfg or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = AxisRules(mesh, seq_parallel=seq_parallel,
                      inference_2d=inference_2d and shape.kind == "decode")
    chips = mesh_chips(mesh)

    t0 = time.time()
    with set_mesh(mesh):
        spec, lowered, compiled = _lower_compile(cfg, shape, rules, tc=tc)
        t_full = time.time() - t0
    t_lower = t_full
    t_compile = time.time() - t0 - t_full

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    f_full = float(ca.get("flops", 0.0))  # XLA: while bodies counted ONCE
    b_full = float(ca.get("bytes accessed", 0.0))
    hlo = compiled.as_text()

    # text-based cost model with exact known_trip_count multipliers
    cost = hlo_cost.analyze(hlo)
    geo_len = (steps.decode_geometry(cfg, shape)["cache_len"]
               if shape.kind == "decode" else shape.seq_len)
    cost_fc = (hlo_cost.analyze(hlo, flash_seq=geo_len)
               if not cfg.is_attention_free() else cost)
    flops, bytes_accessed = cost.flops, cost.bytes
    model_flops = H.model_flops_for(cfg, shape)
    rf = H.roofline_terms(
        hlo_flops=flops, hlo_bytes=bytes_accessed,
        collective_bytes=float(cost.collective_bytes), chips=chips,
        model_flops=model_flops)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": spec["kind"],
        "variant": spec.get("variant", "native"),
        "tags": extra_tags,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "n_params": M.count_params(cfg),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": {"flops": flops, "bytes_accessed": bytes_accessed,
                 "bytes_flash_credited": cost_fc.bytes,
                 "bytes_full_cpu_lowered": cost.bytes_full,
                 "flops_raw_bodyonce": f_full,
                 "bytes_raw_bodyonce": b_full},
        "collectives": {
            "bytes_by_kind": cost.coll,
            "count_by_kind": cost.coll_n,
            "total_bytes": cost.collective_bytes,
        },
        "roofline": rf.row(),
    }
    if verbose:
        mm = rec["memory"]
        print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']} "
              f"({spec['kind']}, {rec['variant']}) OK "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s", flush=True)
        print(f"  memory: args={_gb(mm['argument_bytes'])} "
              f"temp={_gb(mm['temp_bytes'])} peak={_gb(mm['peak_bytes'])}")
        print(f"  cost: flops/dev={flops:.3e} bytes/dev={bytes_accessed:.3e}")
        print(f"  collectives/dev: { {k: f'{v:.2e}' for k, v in cost.coll.items()} }")
        print(f"  roofline: compute={rf.compute_s*1e3:.2f}ms "
              f"memory={rf.memory_s*1e3:.2f}ms "
              f"collective={rf.collective_s*1e3:.2f}ms "
              f"-> {rf.bottleneck}-bound; useful={rf.useful_ratio:.2f}; "
              f"memory(flash-credit)={cost_fc.bytes/819e9*1e3:.2f}ms")
    return rec


def _gb(x):
    return "n/a" if x is None else f"{x/2**30:.2f}GiB"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="input shape (default: all)")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train shapes)")
    ap.add_argument("--infer-2d", action="store_true",
                    help="decode: replicate activations over data; weights "
                         "stay 2D-sharded (no per-step weight gathers)")
    ap.add_argument("--tag", default="", help="tag recorded with each row")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()
    tc = TrainConfig(accum_steps=args.accum)

    archs = [args.arch] if args.arch else [a for a in list_archs()
                                           if not a.startswith("chicle")]
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multipod]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_one(arch, shape, multi_pod=mp,
                                  seq_parallel=args.seq_parallel,
                                  extra_tags=args.tag, tc=tc,
                                  inference_2d=args.infer_2d)
                except Exception as e:  # noqa: BLE001
                    n_fail += 1
                    print(f"[dryrun] {arch} x {shape} x "
                          f"{'2x16x16' if mp else '16x16'} FAILED: {e}",
                          flush=True)
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": str(e)[:500]}
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
