"""Pallas TPU kernels for the paper's compute hot-spots (ensemble eval).

cascade_kernel:  blocked early-exit cascade (the QWYC serving loop).
lattice_kernel:  multilinear lattice interpolation (real-world base models).
tree_kernel:     oblivious-forest evaluation (benchmark GBT base models).
device_executor: the whole cascade stage loop as ONE jit'd device program
                 (DESIGN.md §5).
sharded_executor: that program shard_map'd over a mesh's "data" axis —
                 data-parallel serving with per-shard survivor buffers
                 (DESIGN.md §6).

interpret:       per-call choice of Mosaic (TPU) or interpret mode (CPU).

All validated against pure-jnp oracles in ``ref.py``: in interpret mode on
the CPU by the tests, compiled for a v5e by ``tests/test_tpu_compile.py``.
"""

from repro.kernels import device_executor, ops, ref
from repro.kernels.cascade_kernel import cascade_chunk_pallas, cascade_pallas
from repro.kernels.device_executor import (
    DeviceExecutor,
    DevicePlan,
    BoundScorer,
    lattice_stage_scorer,
    matrix_stage_scorer,
    tree_stage_scorer,
)
from repro.kernels.lattice_kernel import lattice_scores_pallas
from repro.kernels.sharded_executor import ShardedDeviceExecutor
from repro.kernels.tree_kernel import gbt_scores_pallas

__all__ = [
    "ops",
    "ref",
    "device_executor",
    "ShardedDeviceExecutor",
    "cascade_pallas",
    "cascade_chunk_pallas",
    "lattice_scores_pallas",
    "gbt_scores_pallas",
    "DeviceExecutor",
    "DevicePlan",
    "BoundScorer",
    "matrix_stage_scorer",
    "tree_stage_scorer",
    "lattice_stage_scorer",
]
