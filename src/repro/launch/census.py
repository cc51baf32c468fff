import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""HLO buffer/traffic census for perf iterations: compile one combo and
print the largest defining instructions (by total bytes across mentions)
and the per-op-kind byte/flop totals from the trip-count-exact cost model.

    PYTHONPATH=src python -m repro.launch.census --arch arctic-480b \
        --shape decode_32k [--multipod] [--seq-parallel]
"""

import argparse  # noqa: E402
import re  # noqa: E402
from collections import Counter  # noqa: E402

import jax  # noqa: E402
from jax import set_mesh  # noqa: E402

from ..configs import INPUT_SHAPES, TrainConfig, get_config  # noqa: E402
from ..sharding import AxisRules  # noqa: E402
from . import hlo_cost, steps  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

BYTES = {"f32": 4, "bf16": 2, "s32": 4, "pred": 1, "u32": 4, "s8": 1}
DEF = re.compile(r"=\s+(\w+)\[([\d,]+)\]\{[^}]*\}\s+([\w\-]+)\(")


def census(hlo: str, min_bytes: float = 50e6, top: int = 25):
    tot, cnt = Counter(), Counter()
    op_tot = Counter()
    for line in hlo.splitlines():
        m = DEF.search(line)
        if not m:
            continue
        dt, dims, op = m.groups()
        if dt not in BYTES:
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d)
        b = n * BYTES[dt]
        op_tot[op] += b
        if b > min_bytes:
            key = f"{op} {dt}[{dims}]"
            tot[key] += b
            cnt[key] += 1
    print("== largest defining instructions (sum over mentions) ==")
    for k, b in tot.most_common(top):
        print(f"{b/2**30:8.2f}GiB {cnt[k]:4d}x  {k}")
    print("== bytes by op kind (single-mention, no trip counts) ==")
    for k, b in op_tot.most_common(15):
        print(f"{b/2**30:8.2f}GiB  {k}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    shape = INPUT_SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multipod)
    rules = AxisRules(mesh, seq_parallel=args.seq_parallel)
    tc = TrainConfig(accum_steps=args.accum)
    spec = steps.input_specs(cfg, shape, rules, tc)
    step = steps.build_step(cfg, shape, rules, spec)
    with set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=spec["in_shardings"],
                           out_shardings=spec["out_shardings"],
                           donate_argnums=spec["donate_argnums"]
                           ).lower(*spec["args"]).compile()
    hlo = compiled.as_text()
    census(hlo)
    cost = hlo_cost.analyze(hlo)
    print(f"== cost model == flops={cost.flops:.3e} bytes={cost.bytes:.3e} "
          f"bytes_full={cost.bytes_full:.3e}")
    print("collectives:", {k: f"{v:.2e}" for k, v in cost.coll.items()})


if __name__ == "__main__":
    main()
