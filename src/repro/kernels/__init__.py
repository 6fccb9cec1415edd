"""Pallas TPU kernels for the compute hot-spots of DC-kCore.

The paper's per-iteration hot-spot is the h-index estimation over every
node's gathered neighbor estimates (Algorithms 1/2):

* ``hindex/`` — the single-device h-index form: blocked sort-free
  compare-and-reduce straight to the new estimates.
* ``counts/`` — the distributed form: per-shard partial suffix counts
  (the psum payload of core/distributed.py), tiled over candidates so the
  VMEM footprint is width-independent.
* ``fused/`` — the whole sweep body in one kernel per row tile: in-kernel
  neighbor gather + h-index + segment-reduce dirty-bit push, so no
  ``[rows, width]`` intermediate ever round-trips HBM (the
  ``engine="fused"`` path of core/decompose.py).

Every op resolves Pallas interpret mode from the backend
(:func:`resolve_interpret`): interpreted on the CPU, where the kernels are
checked against pure-jnp oracles (tests/test_kernels_*.py,
tests/test_fused_engine.py), and compiled by Mosaic everywhere else.
tests/test_tpu_compile.py compiles them for a described TPU v5e. The fused
kernel does not compile for the TPU yet (:func:`repro.kernels.fused.ops.
require_fused_platform` refuses it there).
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode: ``None`` means "only on the CPU backend".

    An explicit bool wins, so a compile test can ask for the Mosaic
    lowering while the process's own backend is still the CPU.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
