"""Grouped matrix products for a mixture of experts that drops no
token (models/moe.py `dropless_moe`): rows sorted by expert, each
expert's rows multiplied by that expert's matrices.

The rows come in a PADDED layout: every expert's run of rows is
padded to whole sub-tiles of `SUB_ROWS` rows (a bf16 sublane tile),
so a sub-tile belongs to exactly one expert and the kernel needs no
mask. The experts' matrices come STACKED over layers, `[L, E, K, N]`,
with the layer's index a scalar-prefetch operand: the body copies
(layer, expert)'s matrix HBM -> VMEM out of the whole stack. Nothing
slices a layer's 0.8 GB of experts out of the stack first (a `while`
body's dynamic-slice feeding a custom call is a copy).

A GRID STEP IS AN EXPERT'S WHOLE RUN OF ROWS (PR 40; it was one tile
of rows, and the pipeline fetched the next expert's matrices under
this expert's LAST tile alone). Rows, matrices and results all stay
in HBM and the body copies what it needs, because the copy engine
serves what was asked first and the order of the asks decides what
waits: at a run's first chunk the body asks for the next chunk's
rows, then for the NEXT run's matrices (two buffers), and only then
waits for its own, which were asked for a whole run ago. So the
matrices' stream never pauses, an expert's 8 MB hide under all of
the rows before it, and a chunk of rows never queues behind them.
The run is walked in chunks of `CHUNK_ROWS` rows (the MXU's height)
through two buffers: the next chunk's rows (after a run's last
chunk, the next run's first) are copied while this chunk is
multiplied, and a chunk's result is on its way out under the next
chunk. The walk stops at the run's length: the last chunk holds m <
`CHUNK_ROWS / SUB_ROWS` sub-tiles, and its copies and its product
take m sub-tiles' worth (shapes are static, so a switch picks one of
eight by m). An expert that holds no row is no run: its step does
nothing and its matrices are never read. Nothing lies past the last
run: a chip that holds a share of the experts, whose rows' bound is
for the worst deal, pays for the rows that came. A decode batch (one
sub-tile a run) walks one chunk a step and is bound by the matrices'
stream, as it should be; nothing but the runs' lengths tells the two
regimes apart.

The body is also CODE THAT EVERY PROGRAM PAYS FOR AT SET-UP: it is
traced and lowered in Python for each program a server compiles (a
bucket, a chunk length), and eight static shapes of it are compiled
and loaded. So the call is jitted (one trace for every call of the
same shapes, one lowering a program), the body is written for few
equations, and a product spans a slab of columns (`_slab_columns`),
not a matrix (PERF.md section 6, PR 40: 80 s of a server's start
before, none after).

Two kernels: `gate_up` forms silu(x Wg) * (x Wu) in one pass over a
chunk of rows, `down` multiplies by Wd. Both take a whole [K, N]
matrix as one block where it is at most `_BLOCK_BYTES` (2304 x 896
bf16 is 4.1 MB; two such operands in two buffers are 16.5 MB), so
they raise the scoped VMEM limit. A wider matrix (7168 x 2048 bf16 is
29 MB) goes in blocks of columns, the blocks the OUTER grid axis: for
one block of columns the experts go by in order, and after the last
run of one block comes the first run of the next.

K is whole in a block and accumulation is float32, so a row's
product does not depend on the chunk or the slab it rides in. Rows that belong
to no run are never written: the caller gathers none of them.

`expert_mlp_ragged` is the same layout through XLA's
`lax.ragged_dot` (group sizes = the padded runs): the path wherever
the kernels do not run, and the other side of the timing in PERF.md.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import flash_attention as fa

_VMEM_LIMIT = 64 * 1024 * 1024
_BLOCK_BYTES = 8 * 1024 * 1024
_SLAB_BYTES = 2 * 1024 * 1024
SUB_ROWS = 16      # rows of a sub-tile: what a run is padded to
CHUNK_ROWS = 128   # rows of a chunk of the walk


def use_kernel(x, w) -> bool:
    """Static dispatch: the Pallas kernels on a TPU (or under
    DLROVER_TPU_FORCE_KERNELS=1, interpret mode) where the shapes
    tile: lanes of 128 on both widths, rows in sublane tiles."""
    if jax.default_backend() != "tpu" and not fa.force_kernels():
        return False
    k, n = w.shape[-2:]
    return k % 128 == 0 and n % 128 == 0 and x.shape[0] % SUB_ROWS == 0


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


def _slab_columns(k: int, bn: int, itemsize: int) -> int:
    """Columns of a block's [K, bn] that one product takes: the most
    whole 128-lane tiles that divide bn and fit `_SLAB_BYTES` (Mellum2:
    128 of gate and up's 896, 1152 of down's 2304). The compiler
    unrolls a product whole and a run's last chunk has eight static
    shapes, so what a product spans is code: set-up time, cold and
    from the cache."""
    fit = [
        c for c in range(128, bn + 1, 128)
        if bn % c == 0 and k * c * itemsize <= _SLAB_BYTES
    ]
    return max(fit, default=128)


def _gate_up(x, w_gate, w_up):
    g = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    return jax.nn.silu(g) * u


def _down(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _chunk_rows(rows: int) -> int:
    """Rows of a chunk of the walk: `CHUNK_ROWS`, or where the whole
    layout is shorter, the most sub-tiles (a power of two of them)
    that a run in it can hold."""
    chunk = SUB_ROWS
    while chunk * 2 <= min(rows, CHUNK_ROWS):
        chunk *= 2
    return chunk


def _steps(group_rows, blocks: int, per: int):
    """The walk's scalars, from the padded run lengths [E] (whole
    sub-tiles). Per expert: (first row, sub-tiles) of its run. The
    grid's steps are flat over (block of columns, expert); per place
    in that order, and one past the last: the first run AT OR AFTER
    it, as (expert, block of columns, first row, sub-tiles of its
    first chunk; none where the runs have ended). Entry 0 is the
    first run of all, entry s + 1 the run after step s."""
    e = group_rows.shape[0]
    steps = blocks * e
    subs = (group_rows // SUB_ROWS).astype(jnp.int32)
    start = (jnp.cumsum(group_rows) - group_rows).astype(jnp.int32)
    place = jnp.arange(steps + 1, dtype=jnp.int32)
    holds = jnp.concatenate(
        [jnp.tile(subs > 0, blocks), jnp.ones((1,), jnp.bool_)])
    run = jax.lax.cummin(
        jnp.where(holds, place, steps), axis=0, reverse=True)
    expert = run % e
    first = jnp.where(run < steps, jnp.minimum(subs[expert], per), 0)
    return (
        start, subs, expert, jnp.minimum(run // e, blocks - 1),
        start[expert], first,
    )


def _walk_kernel(product, slab, *refs):
    """One grid step: one expert's run of rows against one block of
    columns of its matrices (`product`), a chunk of rows at a time.
    Every operand stays in HBM and the body copies what it needs; the
    copy engine serves what is asked first, so the order of the asks
    is the kernel's: at a run's first chunk the next chunk's rows,
    THEN the next run's matrices (asked the other way round, a chunk
    of rows waits behind 8 MB of matrices), and only then the wait
    for this run's own (the stream never pauses). Two buffers for
    rows, for results and for matrices: a third for matrices timed no
    faster (PERF.md section 6, PR 40).

    Written for few equations (`lax` scalars, one loop, three
    switches): the body is traced and lowered again for every program
    a server compiles, and a prefill bucket's set-up pays for it. So
    the loop's trips are the run's chunks and, at the grid's first
    step, one trip before them that only asks for the first rows, at
    its last step one after them that only waits for the last
    result."""
    (layer, start, subs, run_expert, run_col, run_row, run_first,
     x_hbm, *w_hbm, o_hbm, x_buf, w_buf, o_buf, x_sems, w_sems, state) = refs
    chunk_rows, bn = o_buf.shape[1:]
    per = chunk_rows // SUB_ROWS
    expert = pl.program_id(1)
    step = pl.program_id(0) * pl.num_programs(1) + expert
    last = pl.num_programs(0) * pl.num_programs(1) - 1
    after = step + 1
    n = subs[expert]
    row0 = start[expert]
    col0 = pl.multiple_of(pl.program_id(0) * bn, 128)

    def sized(m, each):
        """`each(rows)` for the rows of m <= `per` sub-tiles, a
        static number (copies and products have static shapes), and
        nothing where m is 0."""
        jax.lax.switch(m, [lambda: None] + [
            functools.partial(each, r * SUB_ROWS)
            for r in range(1, per + 1)])

    def landed(buf, sem, slot, size):
        """Wait for `size` rows into (`sem` 0, the rows' `buf`) or
        out of (1, the results') buffer `slot`: a semaphore counts
        bytes, so the size alone matters."""
        part = buf.at[slot, pl.ds(0, size)]
        pltpu.make_async_copy(part, part, x_sems.at[sem, slot]).wait()

    def matrices(at, slot):
        """Ask for the matrices of the run at or after place `at`
        (one block of columns of one expert's) into buffer `slot`."""
        col = pl.multiple_of(run_col[at] * bn, 128)
        for i, hbm in enumerate(w_hbm):
            pltpu.make_async_copy(
                hbm.at[layer[0], run_expert[at], :, pl.ds(col, bn)],
                w_buf.at[i, slot], w_sems.at[slot]).start()

    @pl.when(step == 0)
    def _first():
        # state: (the rows' buffer of the next chunk, the sub-tiles
        # of the chunk whose result is on its way out, the matrices'
        # buffer of this run); the first run's matrices are nobody's
        # next
        state[0] = 0
        state[1] = 0
        state[2] = 0
        pl.when(run_first[0] > 0)(functools.partial(matrices, 0, 0))

    turn = state[0]
    mine = state[2]
    chunks = jax.lax.div(n + (per - 1), per)

    def walk(c, carry):
        slot = (turn + c) & 1
        left = n - c * per
        m = jax.lax.select(c < 0, 0, jax.lax.max(jax.lax.min(left, per), 0))
        more = left > per
        row = pl.multiple_of(row0 + c * chunk_rows, SUB_ROWS)
        ahead = pl.multiple_of(
            jax.lax.select(more, row + chunk_rows, run_row[after]), SUB_ROWS)

        # the next chunk's rows, or after the run's last chunk the
        # next run's first, before this chunk's are waited for
        def fetch(size):
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(ahead, size)],
                x_buf.at[1 - slot, pl.ds(0, size)],
                x_sems.at[0, 1 - slot]).start()

        sized(
            jax.lax.select(
                more, jax.lax.min(left - per, per), run_first[after]),
            fetch)

        @pl.when((c == 0) & (n > 0))
        def _run_begins():
            pl.when(run_first[after] > 0)(functools.partial(
                matrices, after, 1 - mine))
            for i in range(len(w_hbm)):
                pltpu.make_async_copy(
                    w_buf.at[i, mine], w_buf.at[i, mine],
                    w_sems.at[mine]).wait()

        def chunk(size):
            # m sub-tiles' worth and nothing past them, and the
            # result on its way out under the next chunk
            landed(x_buf, 0, slot, size)
            multiply(size)
            pltpu.make_async_copy(
                o_buf.at[slot, pl.ds(0, size)],
                o_hbm.at[pl.ds(row, size), pl.ds(col0, bn)],
                x_sems.at[1, slot]).start()

        def multiply(size):
            # a slab of columns a trip: the compiler unrolls a product
            # whole, and eight shapes of whole matrices are seconds of
            # compiling that every start of a server pays
            def columns(j, carry):
                cols = pl.ds(pl.multiple_of(j * slab, 128), slab)
                y = product(
                    x_buf[slot, pl.ds(0, size)],
                    *(w_buf[i, mine, :, cols] for i in range(len(w_hbm))))
                o_buf[slot, pl.ds(0, size), cols] = y.astype(o_buf.dtype)
                return carry

            jax.lax.fori_loop(0, bn // slab, columns, 0)

        sized(m, chunk)
        # the chunk before this one has left its buffer before the
        # chunk after this one is multiplied into it
        sized(state[1], functools.partial(landed, o_buf, 1, 1 - slot))
        state[1] = m
        return carry

    jax.lax.fori_loop(
        jax.lax.select(step == 0, -1, 0),
        chunks + jax.lax.select(step == last, 1, 0), walk, 0)
    state[0] = (turn + chunks) & 1
    state[2] = jax.lax.select(n > 0, 1 - mine, mine)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _walk(product, name, out_dtype, interpreted, bn, slab, x, weights, layer,
          group_rows):
    # jitted for its cache: a program calls this once a layer of a
    # period and a server compiles a program a bucket and a chunk
    # length; the kernel's body is traced once for all of them whose
    # shapes are the same, and lowered once a program. `interpreted`
    # (what `_interpret()` says) is an argument only to be part of
    # that cache's key
    rows, k = x.shape
    experts = weights[0].shape[1]
    n = weights[0].shape[-1]
    chunk = _chunk_rows(rows)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n // bn, experts),
        in_specs=[anywhere] * (1 + len(weights)),
        out_specs=anywhere,
        scratch_shapes=[
            pltpu.VMEM((2, chunk, k), x.dtype),
            pltpu.VMEM((len(weights), 2, k, bn), weights[0].dtype),
            pltpu.VMEM((2, chunk, bn), out_dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((3,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_walk_kernel, product, slab),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=fa._interpret(),
        name=name,
    )(layer, *_steps(group_rows, n // bn, chunk // SUB_ROWS), x, *weights)


def _call(product, name, x, weights, layer, group_rows, out_dtype):
    k, n = weights[0].shape[-2:]
    itemsize = weights[0].dtype.itemsize
    bn = _column_block(k, n, itemsize)
    return _walk(
        product, name, jnp.dtype(out_dtype), fa._interpret(), bn,
        _slab_columns(k, bn, itemsize), x, weights, layer, group_rows)


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


def expert_mlp_kernel(x, w_gate, w_up, w_down, group_rows, layer=None):
    """`expert_mlp` through the two Pallas kernels."""
    wg, lay = _stack(w_gate, layer)
    wu, _ = _stack(w_up, layer)
    wd, _ = _stack(w_down, layer)
    with jax.named_scope("moe_experts"):
        h = _call(
            _gate_up, "moe_grouped_gate_up", x, (wg, wu), lay, group_rows,
            x.dtype,
        )
        return _call(
            _down, "moe_grouped_down", h, (wd,), lay, group_rows, x.dtype)


def expert_mlp(x, w_gate, w_up, w_down, group_rows, layer=None):
    """x [rows, D] in the padded layout -> [rows, D] in x's dtype:
    each expert's SwiGLU over its own run of rows. The
    weights are one layer's `[E, D, M]` / `[E, M, D]` or the stack
    over layers with `layer` the (traced) index. `group_rows` [E] are
    the padded run lengths, whole sub-tiles of `SUB_ROWS` rows. Rows past
    the last run come out as whatever the last expert makes of them
    (`lax.ragged_dot`) or as whatever the buffer held (the kernels):
    the caller gathers none of them."""
    if use_kernel(x, w_gate):
        return expert_mlp_kernel(x, w_gate, w_up, w_down, group_rows, layer)
    return expert_mlp_ragged(x, w_gate, w_up, w_down, group_rows, layer)
