"""Speculative decoding for the slot pool: drafters + greedy accept.

Greedy decoding is LOSSLESS to speculate on: if a drafter guesses the next
k tokens and the model scores all k+1 positions (current token + k drafts)
in ONE batched forward, the argmax at position j is — by construction —
exactly the token a sequential greedy decode would emit after consuming the
(matching) prefix.  Accepting the longest matching draft prefix plus the
model's own token at the first mismatch therefore yields a token stream
bit-identical to the non-speculative one, while amortizing per-token
dispatch and KV-read cost over up to k+1 tokens per tick.  This is the
serving analogue of Chicle's thesis: exploit the ALGORITHM's structure
(greedy determinism) to raise useful work per grant, instead of issuing
more micro-dispatches.

Drafters are pluggable and host-side; they never affect correctness, only
the acceptance rate:

- `NgramDrafter` — prompt-lookup decoding: match the longest suffix n-gram
  of the slot's context (prompt + emitted tokens) against its own earlier
  occurrences and propose the continuation.  Zero extra model FLOPs; shines
  on repetitive/extractive streams and on the short argmax cycles small
  models fall into.
- `DraftModelDrafter` — a tiny autoregressive draft model proposes k tokens
  (batched prefill over all active slots + k-1 vectorized decode steps).
  Draft params reshard with the engine on `resize(k)`.

The engine verifies drafts through `models.model.paged_step` /
`verify_step` (one (B, Q=k+1) dispatch) and calls `greedy_accept` per slot.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

from .pages import next_pow2


def greedy_accept(draft: np.ndarray, verified: np.ndarray) -> int:
    """Longest prefix of `draft` matching the model's own argmax stream.

    verified[j] is the model's greedy token AFTER consuming the current
    token and drafts[0..j-1]; the caller emits verified[0..m] (the m
    matching drafts are verified[0..m-1] themselves, plus the model's
    correction/extension at the first mismatch).
    """
    m = 0
    while m < len(draft) and int(draft[m]) == int(verified[m]):
        m += 1
    return m


class NgramDrafter:
    """Prompt-lookup n-gram drafting (suffix match over the slot's own
    context).  For each slot, the longest suffix n-gram (max_ngram down to
    min_ngram) is matched against its most recent earlier occurrence in
    prompt + emitted tokens; the tokens that followed it are the draft.

    max_lookback bounds the scanned context tail so per-tick host work
    stays O(lookback) instead of growing with the stream."""

    dispatches_per_propose = 0  # pure host lookup: no device dispatch

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 max_lookback: int = 512):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"need 1 <= min_ngram <= max_ngram; got "
                             f"({min_ngram}, {max_ngram})")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.max_lookback = max_lookback

    def on_resize(self, mesh, rules) -> None:  # host-only state: no-op
        pass

    def propose(self, contexts: Sequence[np.ndarray],
                k: int) -> List[np.ndarray]:
        return [self._one(np.asarray(c, np.int64)[-self.max_lookback:], k)
                for c in contexts]

    def _one(self, ctx: np.ndarray, k: int) -> np.ndarray:
        n = len(ctx)
        if k <= 0 or n < self.min_ngram + 1:
            return np.empty(0, np.int64)
        for g in range(min(self.max_ngram, n - 1), self.min_ngram - 1, -1):
            pat = ctx[n - g:]
            windows = np.lib.stride_tricks.sliding_window_view(ctx[:-1], g)
            hits = np.nonzero((windows == pat).all(axis=1))[0]
            if not len(hits):
                continue
            # most recent occurrence wins (local loops dominate), but prefer
            # one whose continuation fills the whole k-token draft budget —
            # on a periodic context the latest match sits flush against the
            # suffix and would truncate the draft for no reason
            best = int(hits[-1])
            if n - (best + g) < k:
                for h in hits[::-1]:
                    if n - (int(h) + g) >= k:
                        best = int(h)
                        break
                else:
                    best = int(hits[0])  # earliest = longest continuation
            cont = ctx[best + g: best + g + k]
            if len(cont):
                return cont.astype(np.int64)
        return np.empty(0, np.int64)


class DraftModelDrafter:
    """Tiny draft-model drafting: one batched prefill over every active
    slot's context, then k-1 vectorized decode steps, all jitted (keyed by
    power-of-two batch/length buckets so retraces stay logarithmic).

    The draft model is greedy too, so with `params` == the target model's
    params the drafts are the target's own stream and acceptance is 100% —
    the deterministic upper bound the tests pin down.  `on_resize` re-places
    the (replicated) draft params on the engine's new mesh.
    """

    dispatches_per_propose = 1  # one jitted prefill+scan call per tick

    def __init__(self, cfg, params=None, *, seed: int = 0,
                 max_cached_fns: int = 8):
        import jax

        from ..models import model as M
        self.cfg = cfg
        self.params = (params if params is not None
                       else M.init_params(cfg, jax.random.key(seed)))
        self.max_cached_fns = max(1, max_cached_fns)
        self._fns = {}

    def on_resize(self, mesh, rules) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.params = jax.device_put(self.params, NamedSharding(mesh, P()))

    def _fn(self, nb: int, L: int, k: int):
        from .engine import _lru_get

        def build():
            import jax
            import jax.numpy as jnp

            from ..models import model as M
            cfg = self.cfg

            def propose(params, toks, lens):
                last, cache = M.prefill(cfg, params, toks, rules=None,
                                        remat=False, cache_len=L + k,
                                        true_len=lens)
                tok = jnp.argmax(last[:, -1], -1).astype(jnp.int32)
                if k == 1:
                    return tok[:, None]

                def body(carry, _):
                    tok, cache, pos = carry
                    logits, cache = M.decode_step(cfg, params, cache,
                                                  tok[:, None], pos,
                                                  rules=None)
                    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                    return (nxt, cache, pos + 1), nxt

                # prefill's token is draft 1; k-1 decode steps finish the span
                _, rest = jax.lax.scan(
                    body, (tok, cache, lens.astype(jnp.int32)), None,
                    length=k - 1)
                return jnp.concatenate([tok[None], rest], axis=0).T  # (nb, k)

            return jax.jit(propose)

        # the engine stamps .tracer after construction; standalone drafters
        # fall back to the untraced default
        return _lru_get(self._fns, (nb, L, k), build, self.max_cached_fns,
                        getattr(self, "tracer", None), "draft")

    def propose(self, contexts: Sequence[np.ndarray],
                k: int) -> List[np.ndarray]:
        import jax
        import jax.numpy as jnp

        n = len(contexts)
        if n == 0 or k <= 0:
            return [np.empty(0, np.int64) for _ in range(n)]
        nb = next_pow2(n)
        L = next_pow2(max(max(len(c) for c in contexts), 1))
        toks = np.zeros((nb, L), np.int32)
        lens = np.ones(nb, np.int32)  # pad rows decode garbage, discarded
        for i, c in enumerate(contexts):
            toks[i, : len(c)] = c
            lens[i] = max(len(c), 1)
        out = self._fn(nb, L, k)(self.params, jnp.asarray(toks),
                                 jnp.asarray(lens))
        out = np.asarray(jax.block_until_ready(out))
        return [out[i].astype(np.int64) for i in range(n)]
