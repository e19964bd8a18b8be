"""Pallas TPU kernels for the paper's compute hot-spots.

spinner     — FUSED  f(A . D1 H D0 . x): HD sandwich + implicit-tile
              structured projection + pointwise epilogue in one pass
              (the whole P-model pipeline; see README.md)
fwht        — Walsh-Hadamard transform in MXU (lane-chunk) form
circulant   — block-circulant projection, implicit tile generation, fused f
              (subsumed by spinner; kept as the minimal single-stage kernel)
srf_decode  — fused SRF decode-step state update + readout
paged_gather— page-table gather for the paged serving cache

Each kernel has a pure-jnp oracle in ref.py; ops.py provides the public
wrappers and their routing — native on TPU (or an error), interpret or
the jnp reference on CPU (README.md documents the routing table and VMEM
budget model).
"""
from . import ops, ref
from .ops import (circulant_project, fwht, paged_gather, spinner_plan,
                  spinner_project, srf_decode)

__all__ = ["ops", "ref", "circulant_project", "fwht", "paged_gather",
           "spinner_plan", "spinner_project", "srf_decode"]
