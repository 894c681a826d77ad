"""Pipeline parallelism via collective-permute inside one SPMD program.

Reference parity (SURVEY.md §2.5): ATorch's PP is PiPPy-based — fx graph
split into `PipelineStage`s driven by a TensorPipe RPC network
(atorch/atorch/modules/distributed_modules/compilers/pipe_compiler/
distributed_pippy_compiler.py:91, distributed/distributed.py:505
`_build_pippy_rpc_networks`).

TPU design: no RPC driver. The layer stack (leading L axis) is sharded
over the mesh's "pipe" axis, so each stage holds L/S contiguous layers; a
GPipe schedule runs inside `shard_map` with ONLY the pipe axis manual
(`axis_names={'pipe'}`) — data/fsdp/tensor stay GSPMD-auto, so the layer
body keeps its sharding constraints and XLA still inserts the TP/DP
collectives. Each tick every stage runs its layers on one microbatch and
`ppermute`s the activation to the next stage; autodiff derives the
reverse schedule (backward ppermutes flow the opposite direction).
Bubble fraction is (S-1)/(M+S-1) — pick n_microbatches ≥ pipe degree.
"""

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Tree = Any


def _shard_map_manual(f, mesh, in_specs, out_specs, axis: str):
    """shard_map with only `axis` manual; every other mesh axis stays
    under GSPMD."""
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names={axis},
        check_vma=False,
    )


def _tree_where(pred, a: Tree, b: Tree) -> Tree:
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y), a, b
    )


def _tree_zeros(tree: Tree) -> Tree:
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def num_stages(mesh: Mesh, axis: str = "pipe") -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def pipeline_apply(
    layer_fn: Callable[..., Tree],
    mesh: Mesh,
    stacked_params: Tree,
    state: Tree,
    *aux: Any,
    n_microbatches: int,
    axis: str = "pipe",
) -> Tree:
    """Run a stacked-layer model [L, ...] as a GPipe pipeline.

    layer_fn(layer_params, state, *aux) -> state operates on ONE layer's
    params and a microbatch-shaped state pytree (every leaf's leading dim
    is batch). The full local batch is split into n_microbatches along
    dim 0. Params must have L divisible by the pipe degree; L/S
    contiguous layers land on each stage. aux args are broadcast to every
    stage unchanged (positions, masks...). Returns the state pytree after
    all L layers, same sharding as the input.
    """
    s_pipe = num_stages(mesh, axis)
    if s_pipe == 1:
        def body(c, lp):
            return layer_fn(lp, c, *aux), None

        out, _ = jax.lax.scan(body, state, stacked_params)
        return out

    m = n_microbatches
    t_total = m + s_pipe - 1

    param_specs = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    state_specs = jax.tree_util.tree_map(lambda _: P(), state)
    aux_specs = tuple(
        jax.tree_util.tree_map(lambda _: P(), a) for a in aux
    )

    def local(params_local, state_in, *aux_in):
        idx = jax.lax.axis_index(axis)

        def split(x):
            b = x.shape[0]
            return x.reshape(m, b // m, *x.shape[1:])

        mb = jax.tree_util.tree_map(split, state_in)
        mb0 = jax.tree_util.tree_map(lambda x: x[0], mb)

        def my_layers(h):
            def body(c, lp):
                return layer_fn(lp, c, *aux_in), None

            h, _ = jax.lax.scan(body, h, params_local)
            return h

        def step(carry, t):
            h, outputs = carry
            t_in = jnp.clip(t, 0, m - 1)
            inject = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, t_in, 0, keepdims=False
                ),
                mb,
            )
            h = _tree_where(idx == 0, inject, h)
            h = my_layers(h)
            t_out = t - (s_pipe - 1)
            collect = jnp.logical_and(idx == s_pipe - 1, t_out >= 0)
            t_out_c = jnp.clip(t_out, 0, m - 1)
            updated = jax.tree_util.tree_map(
                lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                    buf, v, t_out_c, 0
                ),
                outputs,
                h,
            )
            outputs = _tree_where(collect, updated, outputs)
            h = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % s_pipe) for i in range(s_pipe)]
            )
            return (h, outputs), None

        (_, outputs), _ = jax.lax.scan(
            jax.checkpoint(step),
            (_tree_zeros(mb0), _tree_zeros(mb)),
            jnp.arange(t_total),
        )
        # only the last stage wrote real outputs (zeros elsewhere); psum
        # over the ring broadcasts them to every stage. 16-bit leaves are
        # summed in f32: XLA's AllReducePromotion miscompiles (crashes)
        # bf16 all-reduce on the CPU backend, and f32 is what the TPU
        # reduction hardware uses anyway.
        def _psum(x):
            if x.dtype in (jnp.bfloat16, jnp.float16):
                return jax.lax.psum(
                    x.astype(jnp.float32), axis
                ).astype(x.dtype)
            return jax.lax.psum(x, axis)

        outputs = jax.tree_util.tree_map(_psum, outputs)
        return jax.tree_util.tree_map(
            lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]),
            outputs,
        )

    return _shard_map_manual(
        local, mesh,
        in_specs=(param_specs, state_specs, *aux_specs),
        out_specs=state_specs,
        axis=axis,
    )(stacked_params, state, *aux)
