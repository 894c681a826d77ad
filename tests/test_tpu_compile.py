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


def _census(compiled):
    """(bytes of temporaries, instructions in all, counts of the
    opcodes that move or multiply) of a compiled program: what a
    model WITHOUT latent attention, a prologue or a held share must
    keep to the byte when those are added beside it."""
    import collections
    import re

    ops = collections.Counter(
        re.findall(r"= \S+ ([a-z\-]+)\(", compiled.as_text()))
    return (
        compiled.memory_analysis().temp_size_in_bytes, sum(ops.values()),
        {o: ops.get(o, 0) for o in (
            "fusion", "custom-call", "scatter", "gather", "copy",
            "dynamic-update-slice", "dynamic-slice", "convolution")},
    )


def _calls(text, kernel):
    """The calls of a Pallas kernel by its name in a compiled
    program's text (a loop's body counts once)."""
    import re

    return len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text))


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


# what one v5e states as its `bytes_limit` (my chip run, PR 51)
V5E_BYTES_LIMIT = 16909336064


def test_mistral_train_step_takes_the_top_rung_on_the_chip(
        topo, monkeypatch):
    """The training cell's step (Mistral-7B-v0.3's widths, 2 layers,
    f32 parameters and AdamW, 2 x 2048 tokens) through
    `remat.LadderStep` with the limit the chip states: the layer scan
    keeps everything ("none", ONE compile), the compiled peak leaves
    the margin free, and the flash kernels are in the program. A PR
    that grows the step's temporaries past the room sees the rung
    fall HERE, before the chip does."""
    import json

    import optax

    from dlrover_tpu.common import trace
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops import attention
    from dlrover_tpu.parallel import accelerate as acc_mod, remat
    from dlrover_tpu.parallel.mesh import MeshSpec

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "mistral-7b-v0.3.train-1chip.json",
    )
    with open(path) as f:
        model = json.load(f)
    run = model["run"]
    cfg = LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        mlp_dim=model["intermediate_size"], max_seq_len=run["max_seq_len"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
    )
    assert cfg.remat and cfg.remat_policy == "auto"
    monkeypatch.setattr(attention, "_tpu_available", lambda: True)
    monkeypatch.setattr(
        acc_mod, "device_memory_bytes", lambda device=None: V5E_BYTES_LIMIT
    )
    acc = acc_mod.accelerate(
        init_params=lambda k: llama.init_params(cfg, k),
        loss_fn=lambda p, b, m: llama.loss_fn(cfg, p, b, mesh=m),
        rules=llama.partition_rules(cfg),
        optimizer=optax.adamw(run["learning_rate"]),
        strategy=acc_mod.Strategy(mesh=MeshSpec.fit(1)),
        devices=topo.devices[:1],
    )
    state = jax.tree_util.tree_map(
        lambda a, sh: S(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(acc.init, jax.random.PRNGKey(0)),
        acc.state_shardings,
    )
    batch = acc.abstract_batch(
        {"tokens": S((run["batch"], run["seq"] + 1), jnp.int32)})
    text = acc.train_step.lower(state, batch).compile().as_text()
    (said,) = [
        r[trace.COUNTS] for r in trace.snapshot()
        if r[trace.NAME] == "remat.ladder"
    ][-1:]
    assert (said["rung"], said["compiled"]) == ("none", 1), said
    assert said["peak_none"] + remat.MARGIN_BYTES <= V5E_BYTES_LIMIT
    assert 0 <= said["room_bytes"] < 2**30, said  # it is the top for now
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert _calls(text, kernel) == 1, kernel  # no forward run twice


def _paged_case(chip, heads, kv_heads, quant, stack=None, slots=SLOTS,
                table_pages=SEQ // PAGE, window=None):
    """Compile the paged kernel for `heads` query heads over a pool of
    `kv_heads` (bf16, or int8 with bf16 scales). `stack` = (L, layer):
    the pool as the forward hands it over, stacked over layers and
    addressed at one of them; None is one layer's pool, the same
    kernel at L = 1."""
    cell = (slots * table_pages + 1, PAGE, kv_heads, CFG.head_dim)
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
    q = S((slots, heads, CFG.head_dim), jnp.bfloat16)
    table = S((slots, table_pages), jnp.int32)
    assert pa.supports(q, pool, table)
    return _compile(
        chip,
        functools.partial(
            pa.paged_attention, impl="kernel", layer=layer, window=window
        ),
        q, pool, table, S((slots,), jnp.int32),
    )


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
    """Llama-2-7B's 32 query heads: the smoke's MHA (n_rep = 1, bf16
    and int8 KV) and Mistral's 8 KV heads."""
    text = _paged_case(chip, CFG.n_heads, kv_heads, quant, stack)
    assert "paged_attention_decode" in text


@pytest.mark.parametrize(
    "heads,kv_heads,quant",
    [(16, 16, False), (16, 16, True), (4, 1, False), (8, 1, True),
     (8, 2, False)],
    ids=["mha-tp2-bf16", "mha-tp2-int8", "one-kv-head-bf16",
         "one-kv-head-int8", "two-kv-heads-bf16"],
)
def test_paged_decode_of_a_shard(chip, heads, kv_heads, quant):
    """What one shard of a tensor-parallel replica hands the kernel:
    the smoke's MHA at tp = 2 (n_rep = 1, int8 too), and GQA down to
    ONE KV head a shard, whose sub-word rows fill no sublane word (the
    pool goes without its head axis) and to the two of a word."""
    _paged_case(chip, heads, kv_heads, quant, stack=(2, 1))


def test_mellum2_full_paged_decode(chip):
    """Mellum2's full layers as the benchmark serves them: 64 slots,
    4 KV heads of 8 query heads each, a table of 224 pages."""
    text = _paged_case(
        chip, 32, 4, False, stack=(2, 1), slots=64, table_pages=224
    )
    assert "paged_attention_decode" in text


def test_paged_head_dim_rule_is_mosaics(chip):
    """A head_dim that is no multiple of 128: Mosaic copies no page
    of such a pool out of HBM ("must be aligned to tiling (128)"),
    which is why `supports()` refuses it on a TPU."""
    cell = (2, N_PAGES, PAGE, 8, 64)
    pool = {"k": S(cell, jnp.bfloat16), "v": S(cell, jnp.bfloat16)}
    q = S((SLOTS, 32, 64), jnp.bfloat16)
    table = S((SLOTS, 32), jnp.int32)
    assert not pa.supports(q, pool, table)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(
            chip,
            functools.partial(pa.paged_attention, impl="kernel", layer=1),
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
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < leaf_bytes
    # as on the commit before the layer loop went over periods (a
    # homogeneous model is a period of one, through the same code):
    # 135,120,896 bytes of temporaries there and here
    assert temp <= 136 * 1000 * 1000, temp
    # and to the byte and the instruction what the commit before
    # latent attention, leading dense layers and held shares compiled
    # to (my AOT compile of 6ba8b28, PR 39): a model without the new
    # fields is the parent's program
    assert _census(compiled) == (135120896, 841, {
        "fusion": 49, "custom-call": 6, "scatter": 2, "gather": 2,
        "copy": 13, "dynamic-update-slice": 1, "dynamic-slice": 10,
        "convolution": 8,
    })


def _mellum2_served(depth):
    """The Mellum2 configuration as the benchmark serves it (the
    published widths, `depth` layers, 64 slots x 3584 positions,
    16-cell pages, 8 steps a dispatch): (cfg, params, pools, ring
    pages, table width), all shapes only."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _mellum2_tiny as tiny
    from dlrover_tpu.models import decode, llama
    from dlrover_tpu.serving.paged_kv import PageAllocator, WindowRings

    slots, max_len, chunk = 64, 3584, 8
    cfg = tiny.config(
        tiny.published_model(depth), dtype=jnp.bfloat16, max_seq_len=max_len
    )
    ring = WindowRings(
        PageAllocator(2, PAGE), 0, cfg.sliding_window, chunk
    ).ring_pages
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))
    )
    pools = jax.eval_shape(
        lambda: decode.init_hybrid_pools(
            cfg, slots * (max_len // PAGE) + 1, slots * ring + 1, PAGE
        )
    )
    return cfg, params, pools, ring, slots, max_len, chunk


def _served_depth():
    import json

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "mellum2-12b-a2.5b.serve-1chip.json",
    )
    with open(path) as f:
        return json.load(f)["num_hidden_layers"]


V5E_HBM_BYTES = int(15.75 * 2**30)


def test_mellum2_chunk_program_fits_the_chip(chip, monkeypatch):
    """The hybrid chunk program (k = 8) at the published widths and
    the depth the benchmark serves: both paged kernels (the window
    one too) and the experts' grouped kernels inside, no copy of
    either class of pages or of a layer's experts, and arguments +
    temporaries under the chip's memory with 1 GB to spare."""
    import re

    from dlrover_tpu.serving import engine

    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    cfg, params, pools, ring, slots, max_len, chunk = _mellum2_served(
        _served_depth()
    )
    i32 = S((slots,), jnp.int32)
    program = engine._build_chunk_program(cfg, -1, None, 0.0, 0, 1.0)
    args = _on_chip(chip, (
        pools, S((slots, max_len // PAGE), jnp.int32), params,
        i32, i32, S((slots,), jnp.bool_), i32, S((slots, 2), jnp.uint32),
    ))
    ring_table = _on_chip(chip, S((slots, ring), jnp.int32))
    compiled = program["paged"].lower(*args, chunk, ring_table).compile()
    text = compiled.as_text()
    for kernel in ("paged_attention_decode_window", "moe_grouped_gate_up",
                   "moe_grouped_down"):
        assert kernel in text
    assert re.search(r"paged_attention_decode(\.\d+)? = ", text)
    big = {
        tuple(pools[c]["k"].shape) for c in pools
    } | {tuple(params["layers"]["we_gate"].shape),
         tuple(params["layers"]["we_down"].shape)}
    big |= {s[1:] for s in big} | {(1,) + s[1:] for s in big}
    moved = [
        m.group(0)
        for m in re.finditer(
            r"%(\S+) = \w+\[([\d,]+)\]\S* "
            r"(dynamic-slice|dynamic-update-slice|copy)\(", text
        )
        if tuple(int(d) for d in m.group(2).split(",")) in big
    ]
    assert not moved, moved
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used + 10**9 < V5E_HBM_BYTES, used
    if _served_depth() == 8:
        # this program to the byte and the instruction, re-pinned at
        # PR 40 (the commit after f550a1b: the grouped kernels walk an
        # expert's whole run a grid step and `dropless_moe` pads runs
        # to 16 rows; f550a1b's read 285138432, 7565): what a later PR
        # that adds beside this path must keep
        assert _census(compiled) == (285009408, 7816, {
            "fusion": 225, "custom-call": 47, "scatter": 12, "gather": 19,
            "copy": 54, "dynamic-update-slice": 1, "dynamic-slice": 15,
            "convolution": 21,
        })


def test_mellum2_largest_prefill_fits_the_chip(chip, monkeypatch):
    """The admission program of the largest prompt bucket (min(4096,
    max_len) = 3584 tokens: 28672 routed pairs a layer, a causal band
    of 1024 on the window layers) beside the resident pools."""
    from dlrover_tpu.serving import engine

    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    cfg, params, pools, ring, slots, max_len, _ = _mellum2_served(
        _served_depth()
    )
    program = engine._build_admit_programs(cfg, max_len)["paged_cold_hybrid"]
    args = _on_chip(chip, (
        pools, S((slots, max_len // PAGE), jnp.int32), params,
        S((max_len,), jnp.int32), S((), jnp.int32),
        S((max_len // PAGE,), jnp.int32), S((ring,), jnp.int32),
        S((), jnp.int32),
    ))
    compiled = program.lower(*args).compile()
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "moe_grouped_gate_up" in text
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used + 10**9 < V5E_HBM_BYTES, used


@pytest.mark.parametrize("tokens", [64, 3584], ids=["decode", "prefill"])
def test_grouped_expert_kernels(chip, tokens):
    """The experts' two kernels at Mellum2's widths: a decode batch's
    padded rows (64 slots) and the largest prefill bucket's (3584
    tokens), an expert's whole run a grid step, whole [2304, 896]
    matrices as blocks."""
    from dlrover_tpu.models import moe
    from dlrover_tpu.ops import grouped_matmul as gmm

    d, m, e, layers = 2304, 896, 64, 2
    rows = moe.dropless_rows(tokens * 8, e)
    text = _compile(
        chip,
        lambda x, wg, wu, wd, sizes: gmm.expert_mlp_kernel(
            x, wg, wu, wd, sizes, layer=1),
        S((rows, d), jnp.bfloat16), S((layers, e, d, m), jnp.bfloat16),
        S((layers, e, d, m), jnp.bfloat16),
        S((layers, e, m, d), jnp.bfloat16), S((e,), jnp.int32),
    )
    assert "moe_grouped_gate_up" in text and "moe_grouped_down" in text


def _gigachat3_served():
    """The GigaChat3.1 configuration as the benchmark serves it (the
    published widths, one dense and four expert layers, 16 of 256
    experts held, 96 slots x 4096 positions, 16-cell pages): (cfg,
    params, pool, slots, max_len), all shapes only."""
    import json
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _gigachat3_tiny as tiny
    from dlrover_tpu.models import decode, llama

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs",
        "gigachat3.1-702b-a36b.serve-1chip-ep16.json",
    )
    with open(path) as f:
        served = json.load(f)
    run = served["run"]
    slots, max_len = run["n_slots"], run["max_len"]
    cfg = tiny.config(
        tiny.published_model(
            served["num_hidden_layers"], served["first_k_dense_replace"]),
        held=tuple(served["experts_held"]), dtype=jnp.bfloat16,
        max_seq_len=max_len,
    )
    assert cfg.vocab_size == served["vocab_size"]
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))
    )
    pool = jax.eval_shape(
        lambda: decode.init_page_pool(
            cfg, slots * (max_len // PAGE) + 1, PAGE)
    )
    return cfg, params, pool, slots, max_len


def _moved_whole(text, shapes):
    """Instructions that slice, restack or copy an array of one of
    `shapes` (or of one layer of it)."""
    import re

    big = set(shapes)
    big |= {s[1:] for s in big} | {(1,) + s[1:] for s in big}
    return [
        m.group(0)
        for m in re.finditer(
            r"%(\S+) = \w+\[([\d,]+)\]\S* "
            r"(dynamic-slice|dynamic-update-slice|copy)\(", text
        )
        if tuple(int(d) for d in m.group(2).split(",")) in big
    ]


def test_latent_paged_decode(chip):
    """`paged_attention_decode_latent` at the published sizes: 96
    slots' 64 absorbed queries of 640 numbers against a pool of
    16-cell pages of 640-number rows, 5 layers stacked."""
    slots, width, rank = 96, 640, 512
    text = _compile(
        chip,
        functools.partial(
            pa.latent_paged_attention, scale=0.14468, rank=rank, layer=3,
            impl="kernel"),
        S((slots, 64, width), jnp.bfloat16),
        {"ckv": S((5, 24577, PAGE, width), jnp.bfloat16)},
        S((slots, 256), jnp.int32), S((slots,), jnp.int32),
    )
    assert "paged_attention_decode_latent" in text


def test_latent_gate_is_mosaics(chip):
    """What `supports_latent` refuses on the chip is what Mosaic
    refuses: a row that is not whole lane tiles (576 numbers, the
    latent and the rotary key as they are)."""
    q = S((SLOTS, 64, 576), jnp.bfloat16)
    pages = {"ckv": S((2, N_PAGES, PAGE, 576), jnp.bfloat16)}
    table = S((SLOTS, 8), jnp.int32)
    assert not pa.supports_latent(q, pages, table, 512)
    with pytest.raises(Exception, match="aligned to tiling|tiling"):
        _compile(
            chip,
            functools.partial(
                pa.latent_paged_attention, scale=0.1, rank=512, layer=1,
                impl="kernel"),
            q, pages, table, S((SLOTS,), jnp.int32),
        )


@pytest.mark.parametrize("tokens", [96, 3072], ids=["decode", "prefill"])
def test_grouped_expert_kernels_in_column_blocks(chip, tokens):
    """The experts' two kernels at GigaChat3.1's widths over the 16
    experts held here: a [7168, 2048] matrix is 29 MB, so it goes in
    blocks of columns; a decode batch's worst-case rows (96 slots)
    and a prefill's (3072 tokens), whatever share of them came."""
    from dlrover_tpu.models import moe
    from dlrover_tpu.ops import grouped_matmul as gmm

    d, m, e, layers = 7168, 2048, 16, 4
    assert gmm._column_block(d, m, 2) == 512
    assert gmm._column_block(m, d, 2) == 1792
    assert gmm._column_block(2304, 896, 2) == 896  # Mellum2's: whole
    rows = moe.dropless_rows(tokens * 8, e)
    text = _compile(
        chip,
        lambda x, wg, wu, wd, sizes: gmm.expert_mlp_kernel(
            x, wg, wu, wd, sizes, layer=1),
        S((rows, d), jnp.bfloat16), S((layers, e, d, m), jnp.bfloat16),
        S((layers, e, d, m), jnp.bfloat16),
        S((layers, e, m, d), jnp.bfloat16), S((e,), jnp.int32),
    )
    assert "moe_grouped_gate_up" in text and "moe_grouped_down" in text


def test_gigachat3_chunk_program_fits_the_chip(chip, monkeypatch):
    """The latent chunk program (k = 8) at the published widths and
    the served depth: the latent kernel and the experts' grouped
    kernels inside, no copy of the pool or of a layer's experts, and
    arguments + temporaries under the chip's memory with 1 GB to
    spare."""
    from dlrover_tpu.serving import engine

    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    cfg, params, pool, slots, max_len = _gigachat3_served()
    i32 = S((slots,), jnp.int32)
    program = engine._build_chunk_program(cfg, -1, None, 0.0, 0, 1.0)
    args = _on_chip(chip, (
        pool, S((slots, max_len // PAGE), jnp.int32), params,
        i32, i32, S((slots,), jnp.bool_), i32, S((slots, 2), jnp.uint32),
    ))
    compiled = program["paged"].lower(*args, 8).compile()
    text = compiled.as_text()
    for kernel in ("paged_attention_decode_latent", "moe_grouped_gate_up",
                   "moe_grouped_down"):
        assert kernel in text
    moved = _moved_whole(text, {
        tuple(pool["ckv"].shape),
        tuple(params["layers"]["we_gate"].shape),
        tuple(params["layers"]["we_down"].shape),
    })
    assert not moved, moved
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used + 10**9 < V5E_HBM_BYTES, used


def test_gigachat3_largest_prefill_fits_the_chip(chip, monkeypatch):
    """The admission program of the largest prompt bucket (max_len =
    4096 tokens: 32768 routed pairs a layer, of which any number may
    land here) beside the resident pool: the flash forward at head
    width 192 and the grouped kernels inside, the prompt's rows
    installed where they lie (no copy of the pool)."""
    from dlrover_tpu.serving import engine

    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    cfg, params, pool, slots, max_len = _gigachat3_served()
    program = engine._build_admit_programs(cfg, max_len)["paged_cold"]
    args = _on_chip(chip, (
        pool, S((slots, max_len // PAGE), jnp.int32), params,
        S((max_len,), jnp.int32), S((), jnp.int32),
        S((max_len // PAGE,), jnp.int32),
    ))
    compiled = program.lower(*args).compile()
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "moe_grouped_gate_up" in text
    assert not _moved_whole(text, {tuple(pool["ckv"].shape)})
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used + 10**9 < V5E_HBM_BYTES, used


def _sdar_served():
    """The SDAR configuration as the benchmark serves it (the
    published widths, 6 layers with all 128 experts, 96 slots x 2048
    positions, 16-cell pages, blocks of 4): (cfg, params, pool, slots,
    max_len, chunk, steps), all shapes only."""
    import json
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _sdar_tiny as tiny
    from dlrover_tpu.models import decode, llama

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "sdar-30b-a3b-chat.serve-1chip.json",
    )
    with open(path) as f:
        served = json.load(f)
    run, gen = served["run"], served["generation"]
    slots, max_len = run["n_slots"], run["max_len"]
    cfg = tiny.published_config(served["num_hidden_layers"])
    assert (cfg.vocab_size, cfg.block_length, cfg.mask_token_id) == (
        served["vocab_size"], gen["block_length"], gen["mask_token_id"])
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))
    )
    pool = jax.eval_shape(
        lambda: decode.init_page_pool(
            cfg, slots * (max_len // PAGE) + 1, PAGE)
    )
    return (cfg, params, pool, slots, max_len, run["chunk"],
            gen["denoising_steps"])


@pytest.mark.parametrize("positions", [4, 8])
def test_block_paged_decode(chip, positions):
    """The one paged walk with a diffusion block's queries as further
    heads, under its own name: 4 positions x 32 heads over 4 K/V
    heads, one length a slot; and the served loop's shape, the carried
    block before it (2 x 4 x 32 = 256 query rows, two lengths: the
    carried rows' one block shorter), in ONE call."""
    cell = (3, 96 * 128 + 1, PAGE, 4, 128)
    pool = {"k": S(cell, jnp.bfloat16), "v": S(cell, jnp.bfloat16)}
    text = _compile(
        chip,
        functools.partial(
            pa.paged_attention, impl="kernel", layer=2, block=4),
        S((96, positions, 32, 128), jnp.bfloat16), pool,
        S((96, 128), jnp.int32), S((96,), jnp.int32),
    )
    assert _calls(text, "paged_attention_decode_block") == 1


def test_flash_forward_with_a_block_mask(chip):
    text = _compile(
        chip,
        functools.partial(fa.flash_attention, block=4),
        S((1, 512, 32, 128), jnp.bfloat16),
        S((1, 512, 4, 128), jnp.bfloat16),
        S((1, 512, 4, 128), jnp.bfloat16),
    )
    assert "flash_attention_fwd" in text


def test_sdar_chunk_program_fits_the_chip(chip, monkeypatch):
    """The diffusion chunk program (8 forwards of two blocks a slot:
    the carried one and the block) at the published widths and the
    served depth: the block's paged call, ONE a layer, and the
    experts' grouped kernels inside, the head over the block's rows
    alone, no copy of the pool or of a layer's experts, and arguments
    + temporaries under the chip's memory with 1 GB to spare."""
    from dlrover_tpu.serving import engine

    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    cfg, params, pool, slots, max_len, chunk, steps = _sdar_served()
    i32 = S((slots,), jnp.int32)
    program = engine._build_diffusion_program(cfg, steps)
    args = _on_chip(chip, (
        pool, S((slots, max_len // PAGE), jnp.int32), params,
        S((slots, cfg.block_length), jnp.int32),
        S((slots, cfg.block_length), jnp.bool_),
        S((slots, cfg.block_length), jnp.int32),
        i32, S((slots,), jnp.bool_), i32,
    ))
    lowered = program["paged"].lower(*args, chunk)
    logits = (slots, cfg.block_length, cfg.vocab_size)
    assert f"tensor<{'x'.join(map(str, logits))}xf32>" in lowered.as_text()
    assert f"tensor<{slots}x{2 * cfg.block_length}x{cfg.vocab_size}" not in (
        lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    for kernel in ("paged_attention_decode_block", "moe_grouped_gate_up",
                   "moe_grouped_down"):
        # one call in the layer loop's body: the pool is walked once
        # a forward and layer
        assert _calls(text, kernel) == 1, kernel
    moved = _moved_whole(text, {
        tuple(pool["k"].shape),
        tuple(params["layers"]["we_gate"].shape),
        tuple(params["layers"]["we_down"].shape),
    })
    assert not moved, moved
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used + 10**9 < V5E_HBM_BYTES, used


def test_sdar_largest_prefill_fits_the_chip(chip, monkeypatch):
    """The admission program of the largest prompt bucket the cell's
    prompts touch (512 tokens) beside the resident pool: the flash
    forward under the block mask and the grouped kernels inside."""
    from dlrover_tpu.serving import engine

    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    cfg, params, pool, slots, max_len, _, _ = _sdar_served()
    program = engine._build_admit_programs(cfg, max_len)["paged_cold"]
    args = _on_chip(chip, (
        pool, S((slots, max_len // PAGE), jnp.int32), params,
        S((512,), jnp.int32), S((), jnp.int32),
        S((max_len // PAGE,), jnp.int32),
    ))
    compiled = program.lower(*args).compile()
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "moe_grouped_gate_up" in text
    assert not _moved_whole(text, {tuple(pool["k"].shape)})
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used + 10**9 < V5E_HBM_BYTES, used


def test_window_paged_decode(chip):
    """The paged kernel with a static window over a ring of 66 pages."""
    cell = (3, 64 * 66 + 1, PAGE, 4, 128)
    pool = {"k": S(cell, jnp.bfloat16), "v": S(cell, jnp.bfloat16)}
    text = _compile(
        chip,
        functools.partial(
            pa.paged_attention, impl="kernel", layer=2, window=1024),
        S((64, 32, 128), jnp.bfloat16), pool, S((64, 66), jnp.int32),
        S((64,), jnp.int32),
    )
    assert "paged_attention_decode_window" in text


def test_flash_forward_with_a_band(chip):
    text = _compile(
        chip,
        functools.partial(fa.flash_attention, window=1024),
        S((1, 3584, 32, 128), jnp.bfloat16),
        S((1, 3584, 4, 128), jnp.bfloat16),
        S((1, 3584, 4, 128), jnp.bfloat16),
    )
    assert "flash_attention_fwd" in text


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
