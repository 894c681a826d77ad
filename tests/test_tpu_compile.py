"""AOT compiles for the chip this repo runs on: the Pallas kernels of
the two main paths (elastic trainer, HTTP server) at Llama-2-7B
widths, handed to the TPU v5e compiler for a chip that is DESCRIBED,
not attached. Interpret mode cannot see what this sees: a block shape
Mosaic's (8, 128) rule refuses, a kernel that asks for more VMEM than
it may use. Nothing runs here, so nothing is said about results or
times — chip_smoke.py does that on the chip.

This file is the ONLY place in the repo that describes a topology.
The call lives in a module-scoped fixture (never at import, in a
skipif, a parametrize argument or conftest.py): loading the TPU's
library takes a per-machine lock, so under pytest-xdist only the
worker that RUNS this file may do it; shardings and shapes are built
from the fixture for the same reason."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops import paged_attention as pa
from dlrover_tpu.ops import quantization as qz

CFG = LlamaConfig.llama2_7b()
SEQ = 2048
SLOTS = 8          # decode rows of one server
PAGE, N_PAGES = 16, 512


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2. Pallas is forced out of interpret mode
    for the module (on the chip it is False by construction; here the
    CPU backend would otherwise pick it) and the persistent compile
    cache is off around the compiles — an executable for a described
    chip can be written but not read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mp = pytest.MonkeyPatch()
    mp.setattr(fa, "_interpret", lambda: False)
    mp.setattr(qz, "_interpret", lambda: False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def chip(topo):
    """One described chip, as the sharding every argument carries."""
    return SingleDeviceSharding(topo.devices[0])


S = jax.ShapeDtypeStruct


def _on_chip(chip, shapes):
    """A pytree of ShapeDtypeStructs, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda s: S(s.shape, s.dtype, sharding=chip), shapes
    )


def _compile(chip, fn, *shapes):
    """Compile `fn` for the described chip; `shapes` are pytrees of
    ShapeDtypeStructs, placed on the chip here. Returns the text."""
    args = _on_chip(chip, shapes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _qkv():
    return S((1, SEQ, CFG.n_heads, CFG.head_dim), jnp.bfloat16)


def test_flash_forward(chip):
    _compile(
        chip,
        functools.partial(fa.flash_attention, causal=True),
        _qkv(), _qkv(), _qkv(),
    )


def test_flash_backward(chip):
    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    _compile(
        chip, jax.grad(loss, argnums=(0, 1, 2)), _qkv(), _qkv(), _qkv()
    )


def test_flash_under_a_training_mesh(topo):
    """The compiler will not partition a Pallas kernel itself
    ("Mosaic kernels cannot be automatically partitioned"): under an
    fsdp x tensor mesh the flash kernel must sit inside a shard_map
    over the batch and head axes. Forward and backward, four
    described chips."""
    from jax.sharding import NamedSharding

    from dlrover_tpu.parallel.mesh import MeshSpec, attention_qkv_spec

    mesh = MeshSpec(fsdp=2, tensor=2).build(topo.devices)
    spec, tp = attention_qkv_spec(mesh)
    assert tp == 2
    sharded = NamedSharding(mesh, spec)

    def loss(q, k, v):
        out = fa.sharded_flash_attention(q, k, v, mesh, causal=True)
        return out.astype(jnp.float32).sum()

    qkv = S(
        (2, SEQ, CFG.n_heads, CFG.head_dim), jnp.bfloat16,
        sharding=sharded,
    )
    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(qkv, qkv, qkv).compile().as_text()
    )
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert kernel in text, kernel


@pytest.mark.parametrize(
    "kv_heads,quant,stack",
    [
        (32, False, None), (32, True, None), (8, False, None),
        (8, False, (3, 2)), (8, True, (3, 2)),
    ],
    ids=["mha-bf16", "mha-int8", "gqa8-bf16", "gqa8-bf16-stacked",
         "gqa8-int8-stacked"],
)
def test_paged_decode(chip, kv_heads, quant, stack):
    """`stack` = (L, layer): the pool as the forward hands it over,
    stacked over layers and addressed at one of them; None is one
    layer's pool, the same kernel at L = 1."""
    cell = (N_PAGES, PAGE, kv_heads, CFG.head_dim)
    layer = None
    if stack is not None:
        cell = (stack[0],) + cell
        layer = stack[1]
    if quant:
        pool = {
            "k": S(cell, jnp.int8), "v": S(cell, jnp.int8),
            "k_scale": S(cell[:-1] + (1,), jnp.bfloat16),
            "v_scale": S(cell[:-1] + (1,), jnp.bfloat16),
        }
    else:
        pool = {"k": S(cell, jnp.bfloat16), "v": S(cell, jnp.bfloat16)}
    q = S((SLOTS, CFG.n_heads, CFG.head_dim), jnp.bfloat16)
    table = S((SLOTS, SEQ // PAGE), jnp.int32)
    assert pa.supports(q, pool, table)
    _compile(
        chip,
        functools.partial(
            pa.paged_attention, impl="kernel", layer=layer
        ),
        q, pool, table, S((SLOTS,), jnp.int32),
    )


def test_paged_chunk_program_walks_pool_in_place(chip, monkeypatch):
    """The serving engine's own paged chunk program (8 decode steps a
    dispatch) at Mistral-7B-v0.3 widths, 48 slots x 1536 positions,
    16-cell pages, 4 layers: the layer loop carries the stacked pool
    and each layer addresses its part by index, so the compiled
    program holds no slice of a layer out of the pool, no restack and
    no copy of the pool — and far less than one leaf of temporaries."""
    import re

    from dlrover_tpu.models import decode, llama
    from dlrover_tpu.serving import engine

    # the dispatch gate asks jax.default_backend(), which is the CPU
    # here: steer it from the test, as the topo fixture steers
    # _interpret
    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    slots, max_len, chunk, layers = 48, 1536, 8, 4
    cfg = LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=layers, n_heads=32,
        n_kv_heads=8, mlp_dim=14336, max_seq_len=max_len,
        rope_theta=1e6, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    n_pages = slots * (max_len // PAGE) + 1
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))
    )
    pool = jax.eval_shape(
        lambda: decode.init_page_pool(cfg, n_pages, PAGE)
    )
    i32 = S((slots,), jnp.int32)
    program = engine._build_chunk_program(cfg, -1, None, 0.0, 0, 1.0)
    # (pool, table, params, tok, pos, done, limit, keys), then k
    args = _on_chip(chip, (
        pool, S((slots, max_len // PAGE), jnp.int32), params,
        i32, i32, S((slots,), jnp.bool_), i32,
        S((slots, 2), jnp.uint32),
    ))
    compiled = program["paged"].lower(*args, chunk).compile()
    text = compiled.as_text()
    assert "paged_attention_decode" in text
    leaf = tuple(pool["k"].shape)
    pool_sized = {leaf, leaf[1:], (1,) + leaf[1:]}
    moved = [
        m.group(0)
        for m in re.finditer(
            r"%(\S+) = \w+\[([\d,]+)\]\S* "
            r"(dynamic-slice|dynamic-update-slice|copy)\(", text
        )
        if tuple(int(d) for d in m.group(2).split(",")) in pool_sized
    ]
    assert not moved, moved
    leaf_bytes = 2 * np.prod(leaf)
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes


def _int8_matmul(chip, rows, k, o):
    blk = qz.weight_quant_block(k)
    assert qz._dqmm_out_tile(k, o), "7B weight must take the kernel"

    def mm(x, q8, s8):
        return qz.quantized_matmul_kernel(
            x, qz.QuantizedWeight(q8, s8, blk)
        )

    _compile(
        chip, mm, S((rows, k), jnp.bfloat16), S((o, k), jnp.int8),
        S((o, k // blk), jnp.float32),
    )


# every distinct [K -> O] of a 7B layer plus the unembed
_WEIGHTS_7B = [
    (CFG.dim, CFG.dim),            # wq/wk/wv/wo
    (CFG.dim, CFG.mlp_dim),        # w_gate/w_up
    (CFG.mlp_dim, CFG.dim),        # w_down — the longest K
    (CFG.dim, CFG.vocab_size),     # lm_head
]


@pytest.mark.parametrize("k,o", _WEIGHTS_7B)
def test_int8_matmul_decode_rows(chip, k, o):
    _int8_matmul(chip, SLOTS, k, o)


@pytest.mark.parametrize("rows", [1024, 2000, 2048])
def test_int8_matmul_prefill_rows(chip, rows):
    """VMEM use must not grow with prompt length: the whole-operand x
    block was refused at these rows; 2000 is a ragged last row tile."""
    _int8_matmul(chip, rows, CFG.mlp_dim, CFG.dim)
    _int8_matmul(chip, rows, CFG.dim, CFG.mlp_dim)


def test_block_quantize(chip):
    _compile(chip, qz.quantize_int8, S((CFG.dim, CFG.dim), jnp.float32))
