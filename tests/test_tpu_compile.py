"""Ahead-of-time compiles of the served path's Pallas kernels for a TPU v5e.

Interpret mode runs every kernel body as ordinary XLA ops and accepts
layouts the chip's compiler (Mosaic) refuses.  These tests compile each
kernel of the main path for a described v5e chip — no chip needed — at
the widths the paper's GBT-500 / lattice-500 cascades serve at: a T=500
model axis (stage slabs of W=8), D=14 features, 4,096 rows, depth-5 trees
and S=8 lattices.  Each asserts that the program really holds a Mosaic
kernel (``tpu_custom_call``), not an interpreted fallback.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU compiler library at a time.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import CascadePlan
from repro.kernels import megakernel as mk
from repro.kernels.cascade_kernel import (
    cascade_chunk_pallas,
    cascade_group_pallas,
    cascade_lane_pallas,
)
from repro.kernels.device_executor import DEFAULT_BLOCK_N, DevicePlan
from repro.kernels.lattice_kernel import lattice_scores_pallas
from repro.kernels.tree_kernel import gbt_scores_pallas

T, D, N, DEPTH, S_FEATS, CHUNK_T = 500, 14, 4096, 5, 8, 8
BN = DEFAULT_BLOCK_N


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache entirely
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, I32 = jnp.float32, jnp.int32


def _dplan():
    rng = np.random.default_rng(0)
    eps = np.abs(rng.normal(size=T)) + 1.0
    plan = CascadePlan(
        order=np.arange(T), eps_pos=eps, eps_neg=-eps, beta=0.0,
        costs=np.ones(T), chunk_t=CHUNK_T,
    )
    return DevicePlan.from_plan(plan)


def _slabs(variant):
    """Real-width f32 ParamSlabs for the megakernel."""
    rng = np.random.default_rng(1)
    dplan = _dplan()
    if variant == "tree":
        slabs = mk.build_tree_slabs(
            dplan,
            rng.integers(0, D, size=(T, DEPTH)).astype(np.int32),
            rng.uniform(size=(T, DEPTH)).astype(np.float32),
            rng.normal(size=(T, 1 << DEPTH)).astype(np.float32),
            quant="f32",
        )
    elif variant == "lattice":
        slabs = mk.build_lattice_slabs(
            dplan,
            rng.normal(size=(T, 1 << S_FEATS)).astype(np.float32),
            np.stack(
                [rng.choice(D, S_FEATS, replace=False) for _ in range(T)]
            ).astype(np.int32),
            quant="f32",
        )
    else:
        slabs = mk.build_matrix_slabs(dplan, quant="f32")
    return dplan, slabs


def _with_data(slabs, data):
    return dataclasses.replace(slabs, data=data)


def test_tree_kernel_compiles(one_chip):
    _compile(
        lambda f, th, lv, x: gbt_scores_pallas(
            f, th, lv, x, block_n=BN, interpret=False
        ),
        one_chip,
        ((T, DEPTH), I32), ((T, DEPTH), F32), ((T, 1 << DEPTH), F32),
        ((N, D), F32),
    )


def test_lattice_kernel_compiles(one_chip):
    _compile(
        lambda th, f, x: lattice_scores_pallas(
            th, f, x, block_n=BN, interpret=False
        ),
        one_chip,
        ((T, 1 << S_FEATS), F32), ((T, S_FEATS), I32), ((N, D), F32),
    )


def test_chunk_decide_compiles(one_chip):
    _compile(
        lambda g, s, ep, en, t0, nv: cascade_chunk_pallas(
            g, s, ep, en, t0, block_n=BN, interpret=False, n_valid=nv
        ),
        one_chip,
        ((N,), F32), ((N, CHUNK_T), F32), ((CHUNK_T,), F32),
        ((CHUNK_T,), F32), ((), I32), ((), I32),
    )


def test_lane_decide_compiles(one_chip):
    _compile(
        lambda g, s, ep, en, nv: cascade_lane_pallas(
            g, s, ep, en, block_n=BN, interpret=False, n_valid=nv
        ),
        one_chip,
        ((N,), F32), ((N, CHUNK_T), F32), ((N, CHUNK_T), F32),
        ((N, CHUNK_T), F32), ((), I32),
    )


def test_group_decide_compiles(one_chip):
    groups, docs = N // 32, 32
    _compile(
        lambda g, v, eps, nl: cascade_group_pallas(
            g, v, eps, 10, interpret=False, n_live=nl
        ),
        one_chip,
        ((groups, docs), F32), ((groups, docs), I32), ((groups,), F32),
        ((), I32),
    )


@pytest.mark.parametrize("variant", ["tree", "lattice", "matrix"])
def test_mega_stage_compiles(one_chip, variant):
    dplan, slabs = _slabs(variant)
    names = sorted(slabs.data)
    width = dplan.T_pad if variant == "matrix" else D

    def step(x, g0, s, t0, nv, eps_pos, eps_neg, scale, *data):
        sl = dataclasses.replace(
            _with_data(slabs, dict(zip(names, data))), scale=scale
        )
        return mk.mega_stage_pallas(
            sl, x, g0, s, t0, nv, eps_pos, eps_neg, block_n=BN,
            interpret=False,
        )

    _compile(
        step, one_chip,
        ((N, width), F32), ((N,), F32), ((), I32), ((), I32), ((), I32),
        ((dplan.S, dplan.W), F32), ((dplan.S, dplan.W), F32),
        ((dplan.S, 1), F32),
        *[(slabs.data[k].shape, slabs.data[k].dtype) for k in names],
    )


@pytest.mark.parametrize("variant", ["tree", "lattice", "matrix"])
def test_mega_lane_compiles(one_chip, variant):
    dplan, slabs = _slabs(variant)
    W = dplan.W
    lanes = {k: (N,) + v.shape[1:] for k, v in slabs.data.items()}
    lanes["scale"] = (N, 1)
    names = sorted(lanes)
    dtypes = dict({k: v.dtype for k, v in slabs.data.items()}, scale=F32)
    width = W if variant == "matrix" else D

    def step(x, g0, ep, en, stop, nv, *data):
        return mk.mega_lane_pallas(
            slabs, x, dict(zip(names, data)), g0, ep, en, stop, nv,
            block_n=BN, interpret=False,
        )

    _compile(
        step, one_chip,
        ((N, width), F32), ((N,), F32), ((N, W), F32), ((N, W), F32),
        ((N,), I32), ((), I32),
        *[(lanes[k], dtypes[k]) for k in names],
    )


def test_full_cascade_decide_compiles(one_chip):
    from repro.kernels.cascade_kernel import cascade_pallas

    _compile(
        lambda s, ep, en: cascade_pallas(
            s, ep, en, 0.0, block_n=BN, chunk_t=CHUNK_T, interpret=False
        ),
        one_chip,
        ((N, T), F32), ((T,), F32), ((T,), F32),
    )


@pytest.mark.parametrize("cap", [256, N])
def test_sort_key_program_compiles(one_chip, cap):
    """The sorted-kernel policy's key program: the stage-0 tree kernel
    and the on-device sort that builds the stage loop's rows buffer, at
    the online and the offline flush capacities."""
    from repro.api import TreeScorer
    from repro.serving.engine import sort_key_program

    rng = np.random.default_rng(2)
    dplan = DevicePlan.from_plan(dataclasses.replace(_dplan().plan, lead_t=1))
    scorer = TreeScorer(
        rng.integers(0, D, size=(T, DEPTH)).astype(np.int32),
        rng.uniform(size=(T, DEPTH)).astype(np.float32),
        rng.normal(size=(T, 1 << DEPTH)).astype(np.float32),
        block_n=BN, interpret=False,
    ).bind(dplan)
    args = [
        jax.ShapeDtypeStruct((cap, D), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((), I32, sharding=one_chip),
    ]
    text = jax.jit(sort_key_program(scorer)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"\bsort\(", text)


def test_expert_kernel_compiles(one_chip):
    """The MoE layer's held-expert kernel at DeepSeek-V2-Lite's widths
    (d_model 2048, expert width 1408, 8 experts held), bfloat16, named so
    that a trace finds it."""
    from repro.kernels.expert_kernel import KERNEL_NAME, TILE_M, _pallas_ffn

    E, Dm, Fw, R = 8, 2048, 1408, 16 * TILE_M
    BF = jnp.bfloat16
    text = jax.jit(lambda x, wi, wg, wo, g: _pallas_ffn(x, wi, wg, wo, g, False)).lower(
        jax.ShapeDtypeStruct((R, Dm), BF, sharding=one_chip),
        jax.ShapeDtypeStruct((E, Dm, Fw), BF, sharding=one_chip),
        jax.ShapeDtypeStruct((E, Dm, Fw), BF, sharding=one_chip),
        jax.ShapeDtypeStruct((E, Fw, Dm), BF, sharding=one_chip),
        jax.ShapeDtypeStruct((E,), I32, sharding=one_chip),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{KERNEL_NAME}[.\d]* = ", text)
