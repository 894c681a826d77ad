"""Pipeline parallelism correctness vs plain layer scan.

Tier-2 (SURVEY.md §4): the GPipe collective-permute schedule must compute
the exact same function as the sequential scan — forward and through a
full optimizer step — on a pipe-sharded virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.models import llama
from dlrover_tpu.parallel.accelerate import Strategy, accelerate
from dlrover_tpu.parallel.mesh import MeshSpec
from dlrover_tpu.parallel.pipeline import pipeline_apply


def test_pipeline_apply_generic():
    """A stack of 4 linear layers pipelined over 2 stages == scan."""
    mesh = MeshSpec(data=2, pipe=2, fsdp=2).build()
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (4, 8, 8)) * 0.3  # [L, D, D]
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))

    def layer_fn(lp, h):
        return jnp.tanh(h @ lp)

    # reference: sequential
    ref = x
    for i in range(4):
        ref = layer_fn(w[i], ref)

    out = jax.jit(
        lambda w, x: pipeline_apply(
            layer_fn, mesh, w, x, n_microbatches=4
        )
    )(w, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6
    )


def test_pipeline_gradients():
    mesh = MeshSpec(pipe=4, data=2).build()
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))

    def layer_fn(lp, h):
        return jnp.tanh(h @ lp)

    def loss_pipe(w):
        return pipeline_apply(
            layer_fn, mesh, w, x, n_microbatches=4
        ).sum()

    def loss_ref(w):
        h = x
        for i in range(4):
            h = layer_fn(w[i], h)
        return h.sum()

    g_pipe = jax.jit(jax.grad(loss_pipe))(w)
    g_ref = jax.grad(loss_ref)(w)
    np.testing.assert_allclose(
        np.asarray(g_pipe), np.asarray(g_ref), rtol=1e-4, atol=1e-5
    )


def test_llama_pipelined_matches_scan():
    cfg0 = llama.LlamaConfig.tiny(dtype=jnp.float32)
    cfg1 = llama.LlamaConfig.tiny(
        dtype=jnp.float32, pipeline_microbatches=2
    )
    mesh = MeshSpec(pipe=2, data=2, fsdp=2).build()
    params = llama.init_params(cfg0, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256)
    base = llama.apply(cfg0, params, tokens)
    piped = jax.jit(
        lambda p, t: llama.apply(cfg1, p, t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(piped), np.asarray(base), rtol=2e-4, atol=2e-4
    )


def test_llama_pipeline_train_step():
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    acc = accelerate(
        lambda key: llama.init_params(cfg, key),
        lambda p, b, mesh: llama.loss_fn(cfg, p, b, mesh),
        llama.partition_rules(cfg),
        optax.adam(1e-3),
        Strategy(mesh=MeshSpec(pipe=2, data=2, fsdp=2)),
    )
    state = acc.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 256)
    batch = acc.shard_batch({"tokens": tokens})
    state, metrics = acc.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
