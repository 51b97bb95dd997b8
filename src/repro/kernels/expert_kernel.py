"""Grouped SwiGLU expert FFN over routed rows: one Pallas kernel.

The dropless MoE layer (``models/moe.py``) lays the (token, slot) pairs
routed to the experts it holds out in one buffer, sorted by expert, each
expert's rows starting on a ``TILE_M`` boundary and padded with zero rows
to a whole number of tiles.  This kernel runs ``silu(x wi_e) * (x wg_e)
@ wo_e`` tile by tile, with ``e`` the tile's expert: the grid's first axis
is the number of tiles in use, so the work follows the routed count and
not the buffer's worst-case size.  The second axis walks the expert width
``F`` in ``tile_f`` columns, accumulating the down projection in float32.

Rows of tiles past the last one in use are left unwritten; callers read
only the rows they routed.  The backward pass (training) is the same
computation through ``jax.lax.ragged_dot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret

__all__ = ["TILE_M", "KERNEL_NAME", "expert_ffn"]

TILE_M = 256  # rows per tile: one expert's weights are read once per tile
KERNEL_NAME = "qwyc_moe_experts"


def _tile_f(f: int) -> int:
    return 128 if f % 128 == 0 else f


def _kernel(tile_expert_ref, x_ref, wi_ref, wg_ref, wo_ref, o_ref, acc_ref):
    del tile_expert_ref  # used by the index maps only
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    h = jnp.dot(x, wi_ref[...], preferred_element_type=jnp.float32)
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * g).astype(wo_ref.dtype)
    acc_ref[...] += jnp.dot(a, wo_ref[...], preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pallas_ffn(x, wi, wg, wo, group_rows, interpret):
    r, d = x.shape
    f = wi.shape[-1]
    tf = _tile_f(f)
    ends = jnp.cumsum(group_rows // TILE_M)  # tiles up to each expert's last
    # expert of tile i: the first whose cumulative tile count passes i
    tile_expert = jnp.searchsorted(
        ends, jnp.arange(r // TILE_M, dtype=ends.dtype), side="right"
    ).astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, wi.shape[0] - 1)
    n_tiles = ends[-1]
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles, f // tf),
            in_specs=[
                pl.BlockSpec((TILE_M, d), lambda i, j, te: (i, 0)),
                pl.BlockSpec((None, d, tf), lambda i, j, te: (te[i], 0, j)),
                pl.BlockSpec((None, d, tf), lambda i, j, te: (te[i], 0, j)),
                pl.BlockSpec((None, tf, d), lambda i, j, te: (te[i], j, 0)),
            ],
            out_specs=pl.BlockSpec((TILE_M, d), lambda i, j, te: (i, 0)),
            scratch_shapes=[pltpu.VMEM((TILE_M, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=resolve_interpret(interpret, x),
        name=KERNEL_NAME,
    )(tile_expert, x, wi, wg, wo)


def _ragged_ffn(x, wi, wg, wo, group_rows):
    """The same computation through ``ragged_dot`` (rows past the groups
    come out zero)."""
    h = jax.lax.ragged_dot(x, wi, group_rows, preferred_element_type=jnp.float32)
    g = jax.lax.ragged_dot(x, wg, group_rows, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * g).astype(wo.dtype)
    return jax.lax.ragged_dot(a, wo, group_rows, preferred_element_type=jnp.float32).astype(
        x.dtype
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def expert_ffn(x, wi, wg, wo, group_rows, interpret=None):
    """x: (R, d) routed rows, R a multiple of ``TILE_M``; wi/wg: (E, d, F);
    wo: (E, F, d); group_rows: (E,) int32 rows of each expert in the
    buffer, each a multiple of ``TILE_M``.  -> (R, d) in x's dtype."""
    return _pallas_ffn(x, wi, wg, wo, group_rows, interpret)


def _fwd(x, wi, wg, wo, group_rows, interpret):
    return _pallas_ffn(x, wi, wg, wo, group_rows, interpret), (x, wi, wg, wo, group_rows)


def _bwd(interpret, res, dy):
    x, wi, wg, wo, group_rows = res
    _, vjp = jax.vjp(lambda *a: _ragged_ffn(*a, group_rows), x, wi, wg, wo)
    zero = np.zeros(group_rows.shape, jax.dtypes.float0)
    return (*vjp(dy), zero)


expert_ffn.defvjp(_fwd, _bwd)
