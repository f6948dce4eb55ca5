"""Pallas TPU kernels for Auxo's clustering hot-spots.

Each kernel ships three artifacts:
  <name>.py  — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     — jit'd public wrappers (padding, dtype policy, interpret switch)
  ref.py     — pure-jnp oracles used by the property tests

BlockSpecs are written for TPU VMEM (last-dim multiples of 128, f32
accumulation). On the CPU backend the kernels run with interpret=True; no
other non-TPU backend is accepted (see ops._interpret).
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
