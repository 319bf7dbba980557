"""Pallas TPU kernels for the perf-critical hot spots, each with a pure-jnp
oracle (ref.py); ops.py and flash_decode.py dispatch by platform (compiled
Pallas on TPU):

* ``flash_attention`` — causal/sliding-window GQA, online softmax, VMEM
  block tiling with causal/window block skipping;
* ``flash_decode``    — the serving decode path: single-token-per-slot
  split-KV attention with per-slot ragged positions, ring/window masking
  and a paged-KV variant (page table in scalar prefetch);
* ``ssd``             — Mamba-2 chunked SSD scan, recurrent state in VMEM
  scratch across the sequential chunk grid;
* ``writhe``          — the paper's workload: Gauss-linking writhe map over
  segment-pair blocks (AlphaKnot's knot screen / knot-core heuristic).
"""
from . import flash_decode, ops, ref

__all__ = ["flash_decode", "ops", "ref"]
