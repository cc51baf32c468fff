"""Real training driver: Chicle elastic data-parallel training of the
assigned architectures on whatever devices exist (CPU here, TPU in prod).

Integrates the full stack: synthetic LM data -> ChunkStore -> uni-task
assignment + policies (elastic schedule, rebalancing) -> ChunkBatchPipeline
(per-example Chicle weights) -> pjit train_step -> checkpointing.

CLI:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --global-batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --scale 100m \
      --steps 300 --elastic 8:4,30:2,60:4
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import set_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import TrainConfig, get_config, smoke_variant
from ..core import (Assignment, ChunkStore, ElasticScalingPolicy,
                    RebalancePolicy, ScaleEvent)
from ..data import ChunkBatchPipeline, make_lm_tokens
from ..checkpoint import save_checkpoint
from ..models import model as M
from ..optim import init_opt_state
from ..sharding import AxisRules
from . import steps
from .cache import init_compile_cache
from .mesh import make_host_mesh


def scale_config(cfg, scale: str):
    """Reduced real-training variants (CPU-sized but non-trivial); "full"
    is the registered config unchanged, at its published widths and its
    own dtype."""
    if scale == "full":
        return cfg
    presets = {
        "tiny": dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
                     head_dim=32, d_ff=256, vocab_size=512),
        "25m": dict(num_layers=6, d_model=384, num_heads=6, num_kv_heads=2,
                    head_dim=64, d_ff=1024, vocab_size=8192),
        "100m": dict(num_layers=12, d_model=640, num_heads=10, num_kv_heads=2,
                     head_dim=64, d_ff=1792, vocab_size=32768),
    }
    upd = dict(presets[scale])
    if cfg.num_experts:
        upd["num_experts"] = min(cfg.num_experts, 4)
    if cfg.encoder_layers:
        upd["encoder_layers"] = 2
        upd["encoder_seq"] = 32
    if cfg.num_image_tokens:
        upd["num_image_tokens"] = 32
    upd["dtype"] = "float32"
    return dataclasses.replace(cfg, **upd)


def parse_elastic(s: Optional[str]):
    """'step:workers,step:workers' -> ScaleEvents keyed on sim_time=step."""
    if not s:
        return []
    out = []
    for part in s.split(","):
        at, n = part.split(":")
        out.append(ScaleEvent(float(at), int(n)))
    return out


def build_data(cfg, *, n_seqs: int, seq_len: int, chunk_size: int, seed: int):
    toks = make_lm_tokens(n_seqs, seq_len, cfg.vocab_size, seed=seed)
    store = ChunkStore({"tokens": toks["tokens"], "labels": toks["labels"]},
                       chunk_size=chunk_size)
    return store


def train(arch: str, *, scale: Optional[str] = None, smoke: bool = False,
          train_steps: int = 50, global_batch: int = 8, seq_len: int = 128,
          workers: int = 4, elastic: Optional[str] = None,
          rebalance: bool = False, hetero: Optional[str] = None,
          ckpt_dir: Optional[str] = None, log_every: int = 10,
          lr: float = 3e-3, seed: int = 0) -> Dict:
    cfg = get_config(arch)
    cfg = smoke_variant(cfg) if smoke else scale_config(cfg, scale or "25m")
    mesh = make_host_mesh()
    rules = AxisRules(mesh)
    tc = TrainConfig(learning_rate=lr, optimizer="sgdm", momentum=0.9,
                     remat=False)

    store = build_data(cfg, n_seqs=max(global_batch * 8, 256),
                       seq_len=seq_len, chunk_size=8, seed=seed)
    assignment = Assignment(store.n_chunks, workers, np.random.default_rng(seed))
    pipe = ChunkBatchPipeline(store, assignment, global_batch=global_batch,
                              seed=seed)
    policies = []
    if elastic:
        policies.append(ElasticScalingPolicy(parse_elastic(elastic)))
    if rebalance:
        policies.append(RebalancePolicy())
    node_pst = (lambda w: 1.0)
    if hetero:  # e.g. "2.0x4" -> first 4 workers 2x slower
        factor, count = hetero.split("x")
        node_pst = (lambda w, f=float(factor), c=int(count):
                    f if w < c else 1.0)

    # state starts where the step leaves it (param sharding, optimizer
    # moments alike), so step 1 reuses step 0's executable; the step
    # donates it, so the old state's buffers hold the new one
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           M.param_specs(cfg, rules),
                           is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(M.init_params(cfg, jax.random.key(seed)),
                            p_shard)
    opt_state = init_opt_state(params, optimizer=tc.optimizer)
    opt_state = jax.device_put(opt_state, type(opt_state)(
        step=NamedSharding(mesh, P()), mu=p_shard,
        nu=None if opt_state.nu is None else p_shard))
    step_fn = jax.jit(steps.make_train_step(cfg, rules, tc),
                      donate_argnums=(0, 1))

    # lightweight engine loop (scheduler phase -> batch -> compiled step)
    sim_time = 0.0
    history = []
    t0 = time.time()
    with set_mesh(mesh):
        for it in range(train_steps):
            stats: Dict = {}

            # elastic schedule is keyed on the STEP index (deterministic)
            eng = type("E", (), {"sim_time": float(it),
                                 "assignment": assignment, "store": store,
                                 "rng": np.random.default_rng(seed + it),
                                 "on_worker_added": lambda *_: None,
                                 "on_worker_removed": lambda *_: None})()
            for p in policies:
                p.between_iterations(eng, stats)

            assignment.begin_iteration()
            batch_np = pipe.next_batch()
            batch = {
                "tokens": jnp.asarray(batch_np["tokens"]),
                "labels": jnp.asarray(batch_np["labels"]),
                "weights": jnp.asarray(batch_np["weights"]),
            }
            if cfg.family in ("audio", "vlm"):
                T = cfg.encoder_seq or cfg.num_image_tokens
                batch["memory"] = jnp.zeros((global_batch, T, cfg.d_model),
                                            cfg.dtype)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            assignment.end_iteration()

            # simulated elastic time: iteration cost = slowest worker
            counts = assignment.sample_counts(store).astype(float)
            shares = counts / max(counts.sum(), 1.0)
            task_times = {w: shares[w] * node_pst(w)
                          for w in range(assignment.n_workers)}
            stats["per_sample_times"] = {w: node_pst(w)
                                         for w in range(assignment.n_workers)}
            stats["task_times"] = task_times
            sim_time += max(task_times.values())
            loss = float(metrics["loss"])
            history.append({"step": it, "loss": loss,
                            "wall_s": time.time() - t0,
                            "workers": assignment.n_workers,
                            "sim_time": sim_time,
                            "events": list(stats.get("scale_events", []))})
            if it % log_every == 0 or it == train_steps - 1:
                print(f"step {it:4d} loss {loss:8.4f} "
                      f"workers {assignment.n_workers:2d} "
                      f"wall {time.time()-t0:6.1f}s", flush=True)
    if ckpt_dir:
        save_checkpoint(ckpt_dir, train_steps, params, opt_state,
                        assignment=assignment)
    return {"history": history, "params": params, "cfg": cfg}


def main() -> None:
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--scale", default=None,
                    choices=[None, "tiny", "25m", "100m", "full"],
                    help="full = the registered config at its published "
                         "widths (TPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--elastic", default=None,
                    help="'step:workers,...' schedule")
    ap.add_argument("--rebalance", action="store_true")
    ap.add_argument("--hetero", default=None, help="e.g. 2.0x4")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    out = train(args.arch, scale=args.scale, smoke=args.smoke,
                train_steps=args.steps, global_batch=args.global_batch,
                seq_len=args.seq, workers=args.workers, elastic=args.elastic,
                rebalance=args.rebalance, hetero=args.hetero,
                ckpt_dir=args.ckpt_dir, lr=args.lr)
    losses = [h["loss"] for h in out["history"]]
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
