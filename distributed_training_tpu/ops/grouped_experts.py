"""The held experts' gated products as ONE Pallas kernel over the rows
that picked them (``dtt_grouped_experts``): the grouped form of
``models/experts.py::expert_layer``.

The caller sorts the (row, pick) pairs that land on a held expert by
expert and gathers their rows into ``xs (M, D)``; group ``e`` is the
rows ``bounds[e]:bounds[e + 1]``. The grid walks the tiles of
``tile_rows`` rows that hold some group's rows, a tile once for each
group it holds (``visits``), and, inside a visit, the expert's hidden
width ``hidden_block`` columns at a time: ``act(x W_g) * (x W_u)``
times the pick's gate, the products and the gate rounded to the rows'
dtype and their product to it once, times ``W_d`` accumulated in
float32. Tiles past the live rows are never visited.
The weights come in STACKED over the layers of a run, ``(L, E, D, F)``,
with the layer's index a prefetched scalar, so that a layer scan hands
the kernel what it carries and nothing is sliced out and copied a
layer (``models/experts.py::layer_of``). Output: float32 ``(M, D)``, a
pick's row of the down product; rows no group holds are left unwritten.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of the three weight blocks a step holds, double-buffered: the
# hidden width a step is the most that keeps them under it.
_WEIGHT_VMEM = 24 << 20
_VMEM_LIMIT = 64 << 20
_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def visits(sizes: jax.Array, tile_rows: int, most: int):
    """``(tile_group, tile_m, count)``: for each of ``most`` visits the
    group and the tile of rows it works on, groups in order and a
    group's tiles in order (so a tile's visits are consecutive), and
    how many visits there are. ``sizes (E,)``: the groups' rows, the
    groups one after another from row 0."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile_rows
    tiles = jnp.where(sizes > 0, (ends - 1) // tile_rows - first + 1, 0)
    upto = jnp.cumsum(tiles)
    v = jnp.arange(most, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(upto, v, side="right"),
                        sizes.shape[0] - 1).astype(jnp.int32)
    tile = first[group] + v - (upto - tiles)[group]
    return group, tile.astype(jnp.int32), upto[-1]


def _kernel(group_ref, tile_ref, bounds_ref, layer_ref, x_ref, g_ref,
            wg_ref, wu_ref, wd_ref, out_ref, acc_ref, *, act, blocks,
            tile_rows):
    del layer_ref
    v, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    dt = x.dtype

    def rounded(a):
        """``a`` rounded to the rows' dtype, as float32."""
        return a.astype(dt).astype(jnp.float32)

    def product(w_ref):
        return rounded(jnp.dot(x, w_ref[...],
                               preferred_element_type=jnp.float32))

    # Where the dense form's compiled fusion rounds: the gate and up
    # products and the gate weight in the rows' dtype, the activation
    # times them in float32, rounded once for the down product.
    h = _ACTS[act](product(wg_ref)) * product(wu_ref) * rounded(g_ref[...])
    acc_ref[...] += jnp.dot(h.astype(dt), wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(f == blocks - 1)
    def _():
        # The tile's rows of this visit's group; a tile that holds two
        # groups is visited twice in a row and keeps its block.
        e = group_ref[v]
        row = (tile_ref[v] * tile_rows
               + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0))
        mine = (row >= bounds_ref[e]) & (row < bounds_ref[e + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])


def hidden_block(D: int, F: int, itemsize: int) -> int:
    """The hidden width a step: all of ``F`` where its three blocks fit
    ``_WEIGHT_VMEM`` double-buffered, else the largest multiple of 128
    under that which divides ``F``; raises where there is none, since a
    step that does not divide ``F`` would leave columns out."""
    fit = _WEIGHT_VMEM // (2 * 3 * D * itemsize)
    if F <= fit:
        return F
    for t in range(fit // 128 * 128, 127, -128):
        if F % t == 0:
            return t
    raise ValueError(
        f"no multiple of 128 up to {fit} divides the expert width {F}: "
        f"its three weight blocks of width {F} at {D} rows do not fit "
        f"{_WEIGHT_VMEM} bytes of VMEM double-buffered")


@functools.partial(jax.jit, static_argnames=("act", "tile_rows",
                                             "interpret"))
def grouped_experts(xs, gates, sizes, wg, wu, wd, layer, *, act: str,
                    tile_rows: int, interpret: bool):
    """``xs (M, D)`` in groups of ``sizes (E,)`` rows, each row's
    ``gates (M,)`` float32, through its group's expert of the stacked
    ``wg``, ``wu (L, E, D, F)`` and ``wd (L, E, F, D)`` at layer
    ``layer``: float32 ``(M, D)``. ``M`` is a multiple of
    ``tile_rows``. Jitted, so that a program that calls it once a run
    of like layers traces the kernel once a set of shapes; the grid's
    first axis is the number of visits, a traced value."""
    M, D = xs.shape
    _L, E, _, F = wg.shape
    tf = hidden_block(D, F, xs.dtype.itemsize)
    blocks = F // tf
    group, tile, count = visits(sizes, tile_rows, M // tile_rows + E - 1)
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(sizes).astype(jnp.int32)])

    def rows(v, f, group, tile, bounds, layer):
        return tile[v], 0

    def up(v, f, group, tile, bounds, layer):
        return layer[0], group[v], 0, f

    def down(v, f, group, tile, bounds, layer):
        return layer[0], group[v], f, 0

    return pl.pallas_call(
        functools.partial(_kernel, act=act, blocks=blocks,
                          tile_rows=tile_rows),
        name="dtt_grouped_experts",
        out_shape=jax.ShapeDtypeStruct((M, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # At least one visit: with no row on any held expert the
            # one visit's group holds no row and writes nothing.
            grid=(jnp.maximum(count, 1), blocks),
            in_specs=[
                pl.BlockSpec((tile_rows, D), rows),
                pl.BlockSpec((tile_rows, 1), rows),
                pl.BlockSpec((None, None, D, tf), up),
                pl.BlockSpec((None, None, D, tf), up),
                pl.BlockSpec((None, None, tf, D), down),
            ],
            out_specs=pl.BlockSpec((tile_rows, D), rows),
            scratch_shapes=[pltpu.VMEM((tile_rows, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(
        group, tile, bounds, jnp.reshape(layer, (1,)).astype(jnp.int32),
        xs, gates[:, None], wg, wu, wd)
