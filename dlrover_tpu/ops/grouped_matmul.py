"""Grouped matrix products for a mixture of experts that drops no
token (models/moe.py `dropless_moe`): rows sorted by expert, each
expert's rows multiplied by that expert's matrices.

The rows come in a PADDED layout: every expert's run of rows starts
on a multiple of `tile` rows, so a tile of rows belongs to exactly one
expert (`tile_group[i]`, a scalar-prefetch operand) and the kernel
needs no mask and revisits no output block. The experts' matrices
come STACKED over layers, `[L, E, K, N]`, with the layer's index as a
second scalar-prefetch operand: the BlockSpec index map resolves
(layer, expert) before the body runs and the pipeline streams that
expert's matrix HBM -> VMEM out of the whole stack. Nothing slices a
layer's 0.8 GB of experts out of the stack first (a `while` body's
dynamic-slice feeding a custom call is a copy). Consecutive tiles of
one expert keep its matrices resident (the block index is unchanged,
so nothing is fetched again).

Two kernels: `gate_up` forms silu(x Wg) * (x Wu) in one pass over a
tile of rows, `down` multiplies by Wd. Both take a whole [K, N]
matrix as one block where it is at most `_BLOCK_BYTES` (2304 x 896
bf16 is 4.1 MB; two such operands double-buffered are 16.5 MB), so
they raise the scoped VMEM limit. A wider matrix (7168 x 2048 bf16 is
29 MB) goes in blocks of columns, the blocks the OUTER grid axis: for
one block of columns the tiles of rows go by in order, so an expert's
block still stays resident over its consecutive tiles.

Where the caller holds a share of the experts its rows' bound is for
the worst deal and most tiles lie past the last run: `live` (int32,
traced) counts the tiles that hold rows, a third scalar-prefetch
operand; a tile past it is not computed, and its index maps name the
last live tile's blocks, so nothing is copied for it either.

`expert_mlp_ragged` is the same layout through XLA's
`lax.ragged_dot` (group sizes = the padded runs): the path wherever
the kernels do not run, and the other side of the timing in PERF.md.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import flash_attention as fa

_VMEM_LIMIT = 64 * 1024 * 1024
_BLOCK_BYTES = 8 * 1024 * 1024


def use_kernel(x, w) -> bool:
    """Static dispatch: the Pallas kernels on a TPU (or under
    DLROVER_TPU_FORCE_KERNELS=1, interpret mode) where the shapes
    tile: lanes of 128 on both widths, rows in sublane tiles."""
    if jax.default_backend() != "tpu" and not fa.force_kernels():
        return False
    k, n = w.shape[-2:]
    return k % 128 == 0 and n % 128 == 0 and x.shape[0] % 16 == 0


def _stack(w, layer):
    """([L, E, K, N] stack, int32[1] layer). One layer's experts
    `[E, K, N]` are the stack with L = 1 (a bitcast)."""
    if w.ndim == 3:
        return w[None], jnp.zeros((1,), jnp.int32)
    return w, jnp.asarray(layer, jnp.int32).reshape(1)


def _column_block(k: int, n: int, itemsize: int) -> int:
    """Columns of one block of a [K, N] matrix: all of them where the
    matrix fits `_BLOCK_BYTES`, else the most whole 128-lane tiles
    that divide N and fit."""
    if k * n * itemsize <= _BLOCK_BYTES:
        return n
    fit = [
        c for c in range(128, n, 128)
        if n % c == 0 and k * c * itemsize <= _BLOCK_BYTES
    ]
    return max(fit) if fit else n


def _gate_up_kernel(*refs):
    x_ref, wg_ref, wu_ref, o_ref = refs[-4:]
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
    o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)


def _down_kernel(*refs):
    x_ref, w_ref, o_ref = refs[-3:]
    o_ref[...] = jnp.dot(
        x_ref[...], w_ref[0, 0], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def _call(kernel, name, x, weights, layer, tile_group, tile, out_dtype,
          live=None):
    rows, k = x.shape
    n = weights[0].shape[-1]
    bn = _column_block(k, n, weights[0].dtype.itemsize)
    skips = live is not None
    prefetch = [layer, tile_group.astype(jnp.int32)]
    if skips:
        prefetch.append(jnp.asarray(live, jnp.int32).reshape(1))

    # grid (blocks of columns, tiles of rows), or where one block
    # holds the whole matrix the tiles of rows alone (the kernel as it
    # was); an index map gets the grid's indices, then the
    # scalar-prefetch operands
    whole = bn == n

    def tile_of(i, scalars):
        # a tile past the live ones names the last live tile's blocks
        if not skips:
            return i
        return jnp.minimum(i, jnp.maximum(scalars[2][0] - 1, 0))

    def grid_map(index):
        if whole:
            return lambda i, *scalars: index(0, i, *scalars)
        return index

    w_map = grid_map(lambda j, i, *scalars: (
        scalars[0][0], scalars[1][tile_of(i, scalars)], 0, j))
    x_map = grid_map(lambda j, i, *scalars: (tile_of(i, scalars), 0))
    o_map = grid_map(lambda j, i, *scalars: (tile_of(i, scalars), j))
    tiles = (rows // tile,)
    grid = tiles if whole else (n // bn,) + tiles

    body = kernel
    if skips:
        def body(*refs):
            @pl.when(pl.program_id(len(grid) - 1) < refs[2][0])
            def _live():
                kernel(*refs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=[pl.BlockSpec((tile, k), x_map)]
        + [pl.BlockSpec((1, 1, k, bn), w_map)] * len(weights),
        out_specs=pl.BlockSpec((tile, bn), o_map),
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=fa._interpret(),
        name=name,
    )(*prefetch, x, *weights)


def _ragged(x, w, layer, group_rows):
    w, layer = _stack(w, layer)
    return jax.lax.ragged_dot(
        x, w[layer[0]].astype(x.dtype), group_rows.astype(jnp.int32),
        preferred_element_type=jnp.float32,
    )


def expert_mlp_ragged(x, w_gate, w_up, w_down, group_rows, layer=None):
    """`expert_mlp` through `lax.ragged_dot`."""
    with jax.named_scope("moe_experts"):
        g = _ragged(x, w_gate, layer, group_rows)
        u = _ragged(x, w_up, layer, group_rows)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        return _ragged(h, w_down, layer, group_rows).astype(x.dtype)


def expert_mlp_kernel(
    x, w_gate, w_up, w_down, tile_group, tile, layer=None, live=None,
):
    """`expert_mlp` through the two Pallas kernels."""
    wg, lay = _stack(w_gate, layer)
    wu, _ = _stack(w_up, layer)
    wd, _ = _stack(w_down, layer)
    with jax.named_scope("moe_experts"):
        h = _call(
            _gate_up_kernel, "moe_grouped_gate_up", x, (wg, wu), lay,
            tile_group, tile, x.dtype, live,
        )
        return _call(
            _down_kernel, "moe_grouped_down", h, (wd,), lay,
            tile_group, tile, x.dtype, live,
        )


def expert_mlp(
    x, w_gate, w_up, w_down, group_rows, tile_group, tile: int, layer=None,
    live=None,
):
    """x [rows, D] in the padded layout -> [rows, D] in x's dtype:
    each expert's SwiGLU over its own run of rows. The
    weights are one layer's `[E, D, M]` / `[E, M, D]` or the stack
    over layers with `layer` the (traced) index. `group_rows` [E] are
    the padded run lengths, `tile_group` [rows / tile] each tile's
    expert. Rows past the last run come out as whatever the last
    expert makes of them, or where `live` counts the tiles that hold
    rows, as whatever the buffer held: the caller gathers none of
    them."""
    if use_kernel(x, w_gate):
        return expert_mlp_kernel(
            x, w_gate, w_up, w_down, tile_group, tile, layer, live)
    return expert_mlp_ragged(x, w_gate, w_up, w_down, group_rows, layer)
