"""Pallas TPU grouped matmul for the routed experts of a mixture of experts.

The rows are (token, expert) entries sorted by expert: group e is the
`group_sizes[e]` consecutive rows routed to expert e, and rows past the
sum of the group sizes belong to no group. The grid walks only the
(m-tile, group) pairs that hold a row of a non-empty group, so an expert
that no row was routed to is never read, and an expert with rows is read
once: its visits are consecutive, and a block is fetched only when its
index changes. Each visit stores only its own group's rows of the tile;
the output tile stays in VMEM across the visits of the groups that
share it.

Two calls make the gated SiLU experts: `silu(x @ w_gate) * (x @ w_up)`,
then `@ w_down`. Each contracts the whole width in one block. Both are
named `moe_experts`, the op's name in the device trace. Rows wider than
the weights (float32 over bfloat16) are contracted in three parts of the
weights' type (`ref.widened`), so the MXU multiplies bfloat16 only and
the rows keep float32's precision; the hidden activation between the
calls keeps the rows' type.

The weights come stacked over the layers, with the layer to use: inside a
layer scan the kernel reads that layer's blocks in place, where a
per-layer slice, an operand of a custom call, would be copied first (1.2
GB a layer at 128 experts of 2048 x 768).

The group metadata and the visit order are adapted from JAX's megablox
`gmm` (jax/experimental/pallas/ops/tpu/megablox/gmm.py, Apache-2.0, The
JAX Authors), without its k/n tiling, sharded groups and transposes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_experts.ref import widened

TM = 128                 # rows of an m-tile
VMEM_LIMIT = 64 << 20    # gate and up blocks at d 2048, f 768: 12 MB


def group_metadata(group_sizes, m: int, tm: int):
    """((group offsets (E+1,), group of each visit, m-tile of each visit),
    number of visits) for `m` rows in tiles of `tm`.

    A group with rows visits every tile from the one holding its first row
    to the one holding its last; an empty group visits none. Visits run in
    group order, so a tile shared by several groups is visited by each in
    turn and its visits are consecutive."""
    e = group_sizes.shape[0]
    tiles = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    group_tiles = jnp.where(group_sizes == 0, 0,
                            (ends + tm - 1) // tm - starts // tm)
    # every tile is visited by the group that holds its first row, and once
    # more by each other group with rows that starts inside it
    inside = (starts % tm != 0) & (group_sizes > 0)
    visits = 1 + jnp.zeros(tiles, jnp.int32).at[starts // tm].add(
        inside.astype(jnp.int32), mode="drop")
    group_ids = jnp.repeat(jnp.arange(e, dtype=jnp.int32), group_tiles,
                           total_repeat_length=tiles + e - 1)
    tile_ids = jnp.repeat(jnp.arange(tiles, dtype=jnp.int32), visits,
                          total_repeat_length=tiles + e - 1)
    return (offsets, group_ids, tile_ids), jnp.sum(group_tiles)


def _dot(x, w):
    # each part's product is exact: the precision is stated, so that none
    # set around the enclosing program reaches the kernel
    return jnp.dot(x, w, preferred_element_type=jnp.float32,
                   precision=lax.Precision.DEFAULT)


def _kernel(offsets, group_ids, tile_ids, layer, x_ref, *refs, tm: int,
            glu: bool):
    *w_refs, o_ref = refs
    i = pl.program_id(0)
    g = group_ids[i]
    row = tile_ids[i] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    x = x_ref[...]
    y = widened(_dot, x, w_refs[0][...])
    if glu:
        y = jax.nn.silu(y) * widened(_dot, x, w_refs[1][...])
    # the tile's rows of other groups keep what their own visits stored
    o_ref[...] = jnp.where(mine, y, o_ref[...].astype(jnp.float32)
                           ).astype(o_ref.dtype)


def _grouped(x, weights, group_sizes, layer, out_dtype, *, tm: int,
             glu: bool, interpret: bool):
    m, k = x.shape
    n = weights[0].shape[3]
    meta, visits = group_metadata(group_sizes, m, tm)

    def rows(i, offsets, group_ids, tile_ids, layer):
        return tile_ids[i], 0

    def expert(i, offsets, group_ids, tile_ids, layer):
        return layer[0], group_ids[i], 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, glu=glu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(visits,),
            in_specs=[pl.BlockSpec((tm, k), rows)]
            + [pl.BlockSpec((None, None, k, n), expert) for _ in weights],
            out_specs=pl.BlockSpec((tm, n), rows),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_experts",          # the op's name in the device trace
    )(*meta, jnp.reshape(layer, (1,)).astype(jnp.int32), x, *weights)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_experts(x, w_gate, w_up, w_down, group_sizes, layer, *,
                tm: int = TM, interpret: bool = False):
    """x: (m, d) rows sorted by expert, m a multiple of `tm`; w_gate and
    w_up: (L, E, d, f); w_down: (L, E, f, d); group_sizes: (E,) int32;
    layer: the int32 index into L. Returns (m, d) float32, row i the gated
    SiLU MLP of its group's expert; rows past the sum of the group sizes
    are left unwritten.

    The hidden activation goes from the first call to the second in the
    rows' type. interpret=True runs the kernel bodies in Python on the
    CPU (the validation mode of the CPU tests)."""
    h = _grouped(x, (w_gate, w_up), group_sizes, layer, x.dtype,
                 tm=tm, glu=True, interpret=interpret)
    return _grouped(h, (w_down,), group_sizes, layer, jnp.float32, tm=tm,
                    glu=False, interpret=interpret)
