"""True device elasticity: REMESH mode (DESIGN.md §2, mode (a)).

The Chicle engine's host-side mode changes worker weights without touching
the compiled step (mode (b), used by launch/train.py).  This module
implements the other half: when the RESOURCE pool itself changes (devices
join/leave), we rebuild the mesh over the active device subset, re-shard the
training state onto it with `jax.device_put`, and swap to a (cached)
train_step compiled for the new mesh — the paper's "spawn/terminate tasks +
redistribute chunks" at the device level.

On this CPU host the device pool is simulated by slicing jax.devices()
(run examples/elastic_remesh.py with XLA_FLAGS=--xla_force_host_platform_
device_count=8 to see real resharding across 8 'nodes').
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax import set_mesh
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, TrainConfig
from ..models import model as M
from ..optim import init_opt_state
from ..sharding import AxisRules
from . import steps
from .mesh import data_mesh


class ElasticTrainer:
    """Recompile-per-K elastic trainer with state carry-over.

    - `resize(k)`: build a mesh over the first k devices, re-shard params +
      optimizer state onto it (device_put — the chunk-transfer analogue for
      model state), and fetch the jit-cached step for that mesh.
    - training state survives every resize; compiled steps are cached per k.
    """

    def __init__(self, cfg: ModelConfig, tc: TrainConfig, *, seed: int = 0):
        self.cfg = cfg
        self.tc = tc
        self.devices = list(jax.devices())
        self.params = M.init_params(cfg, jax.random.key(seed))
        self.opt_state = init_opt_state(self.params, optimizer=tc.optimizer)
        self._cache: Dict[int, Tuple] = {}
        self.k = 0
        self.mesh: Optional[Mesh] = None
        self.suspended = False
        self.resize(len(self.devices))

    def _build(self, k: int):
        mesh = data_mesh(self.devices[:k])
        rules = AxisRules(mesh)
        step = jax.jit(steps.make_train_step(self.cfg, rules, self.tc))
        return mesh, rules, step

    def resize(self, k: int) -> None:
        k = max(1, min(k, len(self.devices)))
        if k == self.k and not self.suspended:
            return
        self.suspended = False
        if k not in self._cache:
            self._cache[k] = self._build(k)
        mesh, rules, step = self._cache[k]
        # re-shard state onto the new device subset (params are replicated
        # over the data mesh in this engine; FSDP variants re-shard the same
        # way with their param specs)
        spec = NamedSharding(mesh, P())
        self.params = jax.device_put(self.params, spec)
        self.opt_state = jax.device_put(self.opt_state, spec)
        self.k, self.mesh, self.rules, self.step = k, mesh, rules, step

    def suspend(self) -> None:
        """Full revocation (cluster scale-to-zero): pull training state to
        host memory, releasing every device lease; `resume(k)` re-shards it
        onto whatever devices come back.  The round-trip is bit-exact —
        training continues as if never interrupted."""
        if self.suspended:
            return
        self.params = jax.device_get(self.params)
        self.opt_state = jax.device_get(self.opt_state)
        self.suspended = True
        self.k = 0
        self.mesh = None

    def resume(self, k: int) -> None:
        self.resize(k)

    def train_step(self, batch: Dict) -> Dict:
        if self.suspended:
            raise RuntimeError("ElasticTrainer is suspended; call resume(k) "
                               "before stepping")
        def shard_for(v):
            spec = P("data") if v.shape[0] % self.k == 0 else P()
            return NamedSharding(self.mesh, spec)

        batch = {k: jax.device_put(v, shard_for(v)) for k, v in batch.items()}
        with set_mesh(self.mesh):
            self.params, self.opt_state, metrics = self.step(
                self.params, self.opt_state, batch)
        return {k: float(v) for k, v in metrics.items()}
