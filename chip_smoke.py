"""Chip smoke test: serving and elastic training on a TPU at smollm-360m's
published widths, through the entry points a user calls.

    python3 chip_smoke.py              # one chip: serve, train, kernel
    python3 chip_smoke.py --chips 4    # only the multi-chip elastic path

One chip runs three phases, in order:

  (a) serve  `launch.serve.serve(scale="full")`, 8 requests (prompts of
             128-384 tokens, 32 new tokens each), three ways: flat KV
             synchronously, paged synchronously, paged with the overlapped
             tick pipeline.  Every request must finish with its tokens, the
             overlapped streams must be bit-equal to the synchronous paged
             streams, and every token of one request per run must be a
             near-argmax of a float32 reference forward.
  (b) train  `launch.train.train(scale="full")`, 5 steps of global batch 8
             at sequence 512; every loss must be finite.
  (c) kernel the compiled paged-attention Pallas kernel at
             h2o-danube-1.8b's attention shapes (Q=1 and Q=5) against the
             jnp oracle.

`--chips 4` runs the multi-chip path and what it is compared with, and
nothing else: `ElasticTrainer` resized 4->2->4 over the same batches
against a fixed 4-chip run, and `ServeEngine.resize` 1->2->4->1 mid-run
against a 1-chip run, with the state's device placement checked after
every resize.

A failed check raises, so the exit code is non-zero.  Throughput, TTFT,
step time and compile seconds are printed as information.  The last line of
standard output is one JSON object naming the device.  Without a TPU the
script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

import numpy as np

ARCH = "smollm-360m"
SEED = 0
BF16_EPS = 2.0 ** -8  # spacing of bfloat16 numbers at 1.0

# An emitted token passes the reference check when its float32 logit lies
# within LOGIT_TOL row standard deviations of the row's max.  bf16 rounding
# in each layer adds an error of about BF16_EPS relative to the activation
# scale; over L layers these add like a random walk, to ~BF16_EPS*sqrt(L)
# of the logit scale.  The tolerance is 8x that.
LOGIT_TOL_PER_SQRT_LAYER = 8 * BF16_EPS
# bf16 kernel output vs the oracle (absolute and relative)
KERNEL_TOL = 8 * BF16_EPS
# elastic 4->2->4 training loss vs the fixed 4-chip run (relative): the
# data-parallel gradient sum is reduced in a different order
LOSS_RTOL = 4 * BF16_EPS

SERVE = dict(requests=8, capacity=8, cache_len=512, page_size=16,
             prefill_bucket=128, prompt_len=(128, 384),
             max_new_tokens=(32, 32), trace="burst", scale_events="",
             seed=SEED)
SERVE_RUNS = (("flat", "flat", False), ("paged", "paged", False),
              ("paged+overlap", "paged", True))
TRAIN = dict(train_steps=5, global_batch=8, seq_len=512, seed=SEED)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.n, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.secs, self.n, self.hits)

    def since(self, mark) -> str:
        s, n, h = mark
        return (f"compile {self.secs - s:.1f}s ({self.n - n} compiles, "
                f"{self.hits - h} persistent-cache hits)")


def _fail(msg: str):
    raise AssertionError(msg)


def _widths(cfg) -> str:
    return (f"{cfg.name}: layers {cfg.num_layers}, d_model {cfg.d_model}, "
            f"heads {cfg.num_heads}/{cfg.kv_heads()}, head_dim "
            f"{cfg.head_dim_()}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
            f"dtype {cfg.dtype}")


class Reference:
    """float32 reference logits: `models.model.forward` over prompt plus
    output, with the serving params cast to float32, at full matmul
    precision."""

    def __init__(self, cfg, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.models import model as M
        cfg32 = dataclasses.replace(cfg, dtype="float32")
        params = M.init_params(cfg, jax.random.key(seed))
        self.params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        self.fn = jax.jit(lambda p, t: M.forward(
            cfg32, p, t, rules=None, remat=False)[0][0])
        self.tol = LOGIT_TOL_PER_SQRT_LAYER * math.sqrt(cfg.num_layers)

    def check(self, req: dict) -> float:
        """Largest (max logit - emitted token's logit) / row std over the
        request's emitted tokens; fails past the tolerance."""
        import jax
        prompt, gen = req["prompt"], req["generated"]
        toks = np.asarray(prompt + gen[:-1], np.int32)[None]
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(self.fn(self.params, toks), np.float64)
        rows = logits[len(prompt) - 1:]  # row i scores generated token i
        gap = ((rows.max(axis=1) - rows[np.arange(len(gen)), gen])
               / rows.std(axis=1))
        worst = float(gap.max())
        if worst > self.tol:
            _fail(f"request {req['rid']}: token {int(gap.argmax())} is "
                  f"{worst:.3f} std below the reference argmax "
                  f"(tolerance {self.tol:.3f})")
        return worst


def serve_phase(clock: CompileClock, scale: str = "full",
                settings: dict = SERVE) -> None:
    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.launch.train import scale_config

    cfg = scale_config(get_config(ARCH), scale)
    print(f"[serve] {_widths(cfg)}", flush=True)
    ref = Reference(cfg, settings["seed"])
    n_new = settings["max_new_tokens"][1]
    streams = {}
    for name, layout, overlap in SERVE_RUNS:
        mark = clock.mark()
        out = serve(ARCH, smoke=False, scale=scale, kv_layout=layout,
                    overlap=overlap, **settings)
        reqs = out["requests"]
        if len(reqs) != settings["requests"]:
            _fail(f"serve[{name}]: {len(reqs)} of {settings['requests']} "
                  f"requests recorded")
        for r in reqs:
            if r["state"] != "finished" or len(r["generated"]) != n_new:
                _fail(f"serve[{name}]: request {r['rid']} ended "
                      f"{r['state']} with {len(r['generated'])} of {n_new} "
                      f"tokens")
        streams[name] = {r["rid"]: r["generated"] for r in reqs}
        gap = ref.check(min(reqs, key=lambda r: r["rid"]))
        ttft = out["ttft_p50_s"]
        print(f"[serve] {name}: {out['requests_finished']}/"
              f"{out['requests_total']} finished, "
              f"{out['tokens_generated']} tokens, "
              f"{out['tokens_per_s']:.1f} tok/s, TTFT p50 "
              f"{ttft * 1e3:.1f} ms, wall {out['wall_s']:.1f}s, "
              f"{clock.since(mark)}; reference: worst gap {gap:.3f} std "
              f"(tolerance {ref.tol:.3f})", flush=True)
    if streams["paged+overlap"] != streams["paged"]:
        diff = [rid for rid in streams["paged"]
                if streams["paged"][rid] != streams["paged+overlap"][rid]]
        _fail(f"serve: overlapped paged streams differ from synchronous "
              f"paged streams for requests {diff}")
    same = sum(streams["flat"][r] == streams["paged"][r]
               for r in streams["flat"])
    print(f"[serve] paged+overlap streams bit-equal to paged; flat and "
          f"paged agree on {same}/{len(streams['flat'])} streams", flush=True)


def train_phase(clock: CompileClock, scale: str = "full",
                settings: dict = TRAIN) -> None:
    from repro.launch.train import train

    mark = clock.mark()
    out = train(ARCH, scale=scale, log_every=1, **settings)
    hist = out["history"]
    losses = [h["loss"] for h in hist]
    if len(losses) != settings["train_steps"] or not all(
            math.isfinite(x) for x in losses):
        _fail(f"train: losses {losses}")
    walls = [h["wall_s"] for h in hist]
    step_s = float(np.median(np.diff(walls))) if len(walls) > 1 else None
    print(f"[train] {_widths(out['cfg'])}", flush=True)
    print(f"[train] {len(losses)} steps, batch {settings['global_batch']} x "
          f"{settings['seq_len']}: losses "
          f"{[round(x, 4) for x in losses]}, step time "
          f"{step_s if step_s is None else f'{step_s * 1e3:.1f} ms'} "
          f"(median after step 0), {clock.since(mark)}", flush=True)


def kernel_phase(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ref
    from repro.kernels.ops import interpret_mode
    from repro.kernels.paged_attention import paged_attention

    cfg = get_config("h2o-danube-1.8b")
    B, KV, hd, ps, P = 8, cfg.kv_heads(), cfg.head_dim_(), 16, 32
    G = cfg.num_heads // KV
    N = B * P + 1
    interpret = interpret_mode()
    rng = np.random.default_rng(SEED)
    for Q in (1, 5):
        mark = clock.mark()
        q = jnp.asarray(rng.standard_normal((B, KV, Q * G, hd)), jnp.bfloat16)
        # a 2-layer stack of pages, layer 1 read
        kp = jnp.asarray(rng.standard_normal((2, N, ps, KV * hd)),
                         jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((2, N, ps, KV * hd)),
                         jnp.bfloat16)
        lengths = rng.integers(Q, P * ps + 1, size=B).astype(np.int32)
        perm = rng.permutation(np.arange(1, N))  # pages shuffled
        table = np.full((B, P), -1, np.int32)
        used = 0
        for b in range(B):
            n = -(-int(lengths[b]) // ps)
            table[b, :n] = perm[used:used + n]
            used += n
        out = paged_attention(q, kp, vp, 1, jnp.asarray(table),
                              jnp.asarray(lengths), q_span=Q,
                              interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_attention_ref(q, kp, vp, 1, jnp.asarray(table),
                                           jnp.asarray(lengths), q_span=Q)
        got = np.asarray(out.astype(jnp.float32))
        want = np.asarray(want.astype(jnp.float32))
        err = float(np.max(np.abs(got - want)))
        if not np.allclose(got, want, atol=KERNEL_TOL, rtol=KERNEL_TOL):
            _fail(f"kernel Q={Q}: max abs error {err:.4g} past tolerance "
                  f"{KERNEL_TOL:.4g}")
        print(f"[kernel] paged_attention interpret={interpret} B={B} "
              f"KV={KV} G={G} hd={hd} page={ps} Q={Q} bf16: max abs error "
              f"{err:.4g} (tolerance {KERNEL_TOL:.4g}), {clock.since(mark)}",
              flush=True)


def _device_counts(tree) -> set:
    import jax
    return {len(x.sharding.device_set) for x in jax.tree.leaves(tree)}


def elastic_train_check(clock: CompileClock, cfg, sizes=(4, 4, 2, 2, 4, 4),
                        batch: int = 8, seq: int = 512) -> None:
    from repro.configs import TrainConfig
    from repro.data import make_lm_tokens
    from repro.launch.elastic import ElasticTrainer

    mark = clock.mark()
    tc = TrainConfig(learning_rate=3e-3, optimizer="sgdm", momentum=0.9,
                     remat=False)
    data = make_lm_tokens(batch * len(sizes), seq, cfg.vocab_size, seed=SEED)
    batches = [{"tokens": data["tokens"][i * batch:(i + 1) * batch],
                "labels": data["labels"][i * batch:(i + 1) * batch],
                "weights": np.ones((batch,), np.float32)}
               for i in range(len(sizes))]
    fixed = ElasticTrainer(cfg, tc, seed=SEED)
    k_max = fixed.k
    want = [fixed.train_step(b)["loss"] for b in batches]
    del fixed
    elastic = ElasticTrainer(cfg, tc, seed=SEED)
    got = []
    for k, b in zip(sizes, batches):
        elastic.resize(k)
        placed = _device_counts((elastic.params, elastic.opt_state))
        if placed != {min(k, k_max)}:
            _fail(f"elastic train: after resize({k}) the state spans "
                  f"{placed} devices")
        got.append(elastic.train_step(b)["loss"])
    if not np.allclose(got, want, rtol=LOSS_RTOL, atol=0):
        _fail(f"elastic train: losses {got} vs fixed {k_max}-device run "
              f"{want} (rtol {LOSS_RTOL})")
    print(f"[elastic-train] {_widths(cfg)}", flush=True)
    print(f"[elastic-train] sizes {list(sizes)}: losses "
          f"{[round(x, 5) for x in got]} vs fixed {k_max}-device "
          f"{[round(x, 5) for x in want]} (rtol {LOSS_RTOL}), "
          f"{clock.since(mark)}", flush=True)


def elastic_serve_check(clock: CompileClock, cfg,
                        schedule=((3, 2), (6, 4), (10, 1)),
                        n_requests: int = 8, prompt_len=(65, 128),
                        max_new: int = 16, cache_len: int = 256) -> None:
    import jax
    from repro.serve import ServeEngine, synthetic_requests

    # float32 at full matmul precision: the streams are compared for
    # equality, so no bf16 near-tie may flip with the per-device batch
    cfg = dataclasses.replace(cfg, dtype="float32")
    mark = clock.mark()

    def requests():
        return synthetic_requests(
            n_requests, vocab_size=cfg.vocab_size,
            arrivals=np.zeros(n_requests), prompt_len=prompt_len,
            max_new_tokens=(max_new, max_new),
            rng=np.random.default_rng(SEED))

    def engine():
        return ServeEngine(cfg, capacity=8, cache_len=cache_len,
                           prefill_bucket=128, n_workers=1, seed=SEED)

    with jax.default_matmul_precision("highest"):
        base = engine()
        want = {r.rid: list(r.generated) for r in base.run(requests()).requests}
        del base
        eng = engine()
        reqs = requests()
        eng.submit(reqs)
        resizes = dict(schedule)
        placements = []
        tick = 0
        while any(r.state.value != "finished" for r in reqs):
            if tick in resizes:
                k = resizes[tick]
                eng.resize(k)
                placed = (_device_counts(eng.params)
                          | _device_counts(eng.blocks))
                if placed != {k}:
                    _fail(f"elastic serve: after resize({k}) the state "
                          f"spans {placed} devices")
                placements.append(k)
            if tick > 1000:
                _fail("elastic serve: requests did not finish in 1000 ticks")
            with jax.set_mesh(eng.mesh):
                eng.tick()
            tick += 1
    got = {r.rid: list(r.generated) for r in reqs}
    if placements != [k for _, k in schedule]:
        _fail(f"elastic serve: resized through {placements}, the run ended "
              f"before schedule {list(schedule)}")
    if got != want:
        diff = [rid for rid in want if got.get(rid) != want[rid]]
        _fail(f"elastic serve: streams differ from the 1-device run for "
              f"requests {diff}")
    print(f"[elastic-serve] {_widths(cfg)}", flush=True)
    print(f"[elastic-serve] resize 1->{'->'.join(map(str, placements))} "
          f"over {tick} ticks: {len(got)} streams equal to the 1-device "
          f"run, {clock.since(mark)}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip elastic path")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import init_compile_cache
    cache_dir = init_compile_cache()
    clock = CompileClock()
    t0 = time.perf_counter()
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)
    if args.chips == 4:
        from repro.configs import get_config
        cfg = get_config(ARCH)
        elastic_train_check(clock, cfg)
        elastic_serve_check(clock, cfg)
    else:
        serve_phase(clock)
        train_phase(clock)
        kernel_phase(clock)
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f}s, {clock.since((0.0, 0, 0))}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
