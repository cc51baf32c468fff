"""Pallas TPU kernels for the framework's compute hot-spots.

- flash_attention: causal/sliding-window attention (VMEM-tiled online softmax)
- paged_attention: block-table paged decode attention over an HBM page
  pool; each grid step covers a block of pages (ppb chosen from the
  shapes), fetched by double-buffered page DMAs whose pages a
  scalar-prefetched fetch plan names, O(live tokens) per sequence;
  `python -m repro.kernels.paged_attention --selftest` for CPU interpret
  parity
- scd: CoCoA local SCD sequential solver (VMEM-resident chunks)
- chunk_reduce: weighted uni-task update merge (bandwidth-bound reduction)

ops.py holds the jit'd model-layout wrappers; ref.py the pure-jnp oracles.
The paper itself has no GPU kernels (CPU/RDMA system); these are the
TPU-native hot spots of THIS framework — see DESIGN.md §6.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
