"""Latent attention (MLA) served through the normal path, against the
plain reference of GigaChat3.1's block (tests/reference_models/
gigachat3.py) on seeded random weights at a test's size: one chip's
share of the experts (8 of 32 held), one leading dense layer, a
shared expert, the sigmoid router with groups and a bias."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _gigachat3_tiny as tiny  # noqa: E402
from dlrover_tpu.common import trace  # noqa: E402
from dlrover_tpu.models import decode, llama  # noqa: E402
from dlrover_tpu.ops import flash_attention as fa  # noqa: E402
from dlrover_tpu.ops import paged_attention as pa  # noqa: E402
from dlrover_tpu.serving.engine import ContinuousBatcher  # noqa: E402
from reference_models import gigachat3 as ref  # noqa: E402

HELD = (8, 8)
# float32 through and through: the program and the reference differ
# by the order of their sums (absorbed against expanded, an online
# softmax, sorted experts); logits are of order 4
TOL = 2e-4


@pytest.fixture(scope="module")
def served():
    """(model, the share's published tree, the program's config and
    tree, 24 tokens, the reference's logits of them)."""
    model = tiny.model_dict()
    tree = tiny.share(model, tiny.params(model, seed=1), HELD)
    cfg = tiny.config(model, HELD, attn_impl="reference")
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, size=24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(model, tree, tokens, held=HELD)
    return model, tree, cfg, tiny.to_program(model, tree), tokens, want


def _paged_logits(cfg, params, tokens, prompt, pool_dtype=None):
    """Prefill `prompt` tokens (expanded), install their rows into a
    slot's pages, decode the rest through the latent pool (absorbed):
    the logits of positions prompt - 1 .. end."""
    pool = decode.init_page_pool(cfg, 9, 8)
    if pool_dtype is not None:
        pool = {"ckv": pool["ckv"].astype(pool_dtype)}
    table = jnp.asarray([[3, 5, 1, 7]], jnp.int32)
    row = decode.prefill_exact_row(cfg, params, tokens[:prompt], 32)
    pool = decode.paged_install_row(pool, row, table[0], 0, prompt)
    out = []
    for t in range(prompt - 1, tokens.shape[0]):
        logits, pool, counts = decode.paged_decode_step(
            cfg, params, tokens[t:t + 1], pool, table, jnp.asarray([t]))
        out.append(logits[0])
    # the pairs per held expert and, a share being held, whether each
    # got one at all, summed over the two expert layers
    assert counts.shape == (2, HELD[1])
    assert (counts[1] <= 2).all() and (counts[1] <= counts[0]).all()
    return jnp.stack(out)


def test_prefill_then_paged_decode_match_the_reference(served):
    _, _, cfg, params, tokens, want = served
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(cfg, params, tokens, 16)
    assert float(jnp.abs(got - want[15:]).max()) < TOL


def test_a_bfloat16_pool_fails_the_float32_tolerance(served):
    """The tolerance is tight enough that a cache one precision lower
    than the configuration states does not pass it."""
    _, _, cfg, params, tokens, want = served
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(cfg, params, tokens, 16, jnp.bfloat16)
    assert float(jnp.abs(got - want[15:]).max()) > 10 * TOL


def test_dense_bank_prefill_and_decode_match_the_reference(served):
    _, _, cfg, params, tokens, want = served
    with jax.default_matmul_precision("highest"):
        cache = decode.init_kv_cache(cfg, 1, 32)
        logits, cache = decode.prefill(cfg, params, tokens[None, :16], cache)
        assert float(jnp.abs(logits[0] - want[15]).max()) < TOL
        for t in range(16, 24):
            logits, cache = decode.decode_step(
                cfg, params, tokens[t:t + 1], cache, jnp.asarray([t]))
            assert float(jnp.abs(logits[0] - want[t]).max()) < TOL
    assert set(cache) == {"ckv"}
    assert cache["ckv"].shape == (3, 1, 32, cfg.latent_width)


def test_absorbed_and_expanded_forms_agree(served):
    """The same weights through both forms: in the reference, and in
    the program (a prefill from 0 is expanded; the same tokens at
    stated positions over the bank are absorbed)."""
    model, tree, cfg, params, tokens, want = served
    with jax.default_matmul_precision("highest"):
        absorbed = ref.forward(model, tree, tokens, held=HELD, form="absorbed")
        assert float(jnp.abs(absorbed - want).max()) < TOL
        positions = jnp.arange(24)[None]
        logits = {}
        for form, plain in (("expanded", True), ("absorbed", False)):
            logits[form], _ = decode._forward_cached(
                cfg, params, tokens[None], decode.init_kv_cache(cfg, 1, 32),
                positions, 0, plain_causal=plain)
    assert float(jnp.abs(logits["expanded"][0] - want).max()) < TOL
    assert float(jnp.abs(logits["absorbed"][0] - want).max()) < TOL


# ---- the latent variant of the paged kernel -------------------------------

PS, W, RANK, PER_ROW = 16, 256, 128, 50


def _latent_case(lengths, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((b, 8, W)), dtype)
    pool = jnp.asarray(
        rng.standard_normal((2, b * PER_ROW + 1, PS, W)), dtype)
    table = jnp.asarray(
        1 + rng.permutation(b * PER_ROW).reshape(b, PER_ROW), jnp.int32)
    return q, {"ckv": pool}, table, jnp.asarray(lengths, jnp.int32)


def _both(q, pages, table, lengths):
    return tuple(
        np.asarray(pa.latent_paged_attention(
            q, pages, table, lengths, 0.2, RANK, layer=1, impl=impl,
        ), np.float32)
        for impl in ("kernel", "reference")
    )


@pytest.mark.parametrize(
    "length", [0, 1, 639, 640, 641, PER_ROW * PS],
    ids=lambda n: f"len{n}",
)
def test_latent_kernel_lengths_around_a_blocks_boundary(length):
    """f32 rows of 256 numbers walk 640 cells a block (40 pages of
    the table's 50): empty, one cell, one under, at and one over the
    block's boundary, and the whole table (a last block of 10 pages),
    against the gathered view."""
    case = _latent_case([length, 200])
    assert pa._latent_pages_per_block(case[1]["ckv"], case[2]) == 40
    ker, want = _both(*case)
    if length == 0:
        # the reference's softmax over no column is NaN; the kernel
        # writes zeros, and the row beside it is untouched by it
        assert not ker[0].any()
        ker, want = ker[1:], want[1:]
    np.testing.assert_allclose(ker, want, atol=2e-5, rtol=2e-5)


def test_latent_kernel_reads_no_page_past_the_length():
    """Every page past a slot's length, and every page of nobody,
    holds NaN; the output is finite and the clean pool's."""
    q, pages, table, lengths = _latent_case([5, 700, 177])
    clean, want = _both(q, pages, table, lengths)
    live = np.zeros(pages["ckv"].shape[1], bool)
    for row, n in enumerate(np.asarray(lengths)):
        live[np.asarray(table)[row, : -(-int(n) // PS)]] = True
    arr = np.asarray(pages["ckv"]).copy()
    arr[:, ~live] = np.nan
    ker = np.asarray(pa.latent_paged_attention(
        q, {"ckv": jnp.asarray(arr)}, table, lengths, 0.2, RANK, layer=1,
        impl="kernel"))
    assert np.isfinite(ker).all()
    np.testing.assert_array_equal(ker, clean)
    np.testing.assert_allclose(ker, want, atol=2e-5, rtol=2e-5)


def test_latent_kernel_in_bfloat16():
    case = _latent_case([700, 33, PER_ROW * PS], dtype=jnp.bfloat16)
    ker, want = _both(*case)
    np.testing.assert_allclose(ker, want, atol=3e-2, rtol=3e-2)


def test_latent_gate_states_what_the_kernel_takes(monkeypatch):
    q, pages, table, _ = _latent_case([3, 4])
    assert pa.supports_latent(q, pages, table, RANK)
    # another width than the pool's rows, a rank past them, heads
    # that fill no sublane tile, a pool of another dtype
    assert not pa.supports_latent(q[..., :128], pages, table, RANK)
    assert not pa.supports_latent(q, pages, table, W + 1)
    assert not pa.supports_latent(q[:, :5], pages, table, RANK)
    assert not pa.supports_latent(
        q.astype(jnp.bfloat16), pages, table, RANK)
    # on the chip Mosaic copies whole 128-lane tiles only
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    assert pa.supports_latent(q, pages, table, RANK)
    assert not pa.supports_latent(q, pages, table, 96)
    narrow = {"ckv": pages["ckv"][..., :192]}
    assert not pa.supports_latent(q[..., :192], narrow, table, RANK)
    assert not pa.use_kernel_latent(q, pages, table, RANK)  # the CPU


# ---- the engine ------------------------------------------------------------


def _greedy(model, tree, prompt, n):
    toks = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(n):
            logits = ref.forward(
                model, tree, jnp.asarray(toks, jnp.int32), held=HELD)
            toks.append(int(jnp.argmax(logits[-1])))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def prompts_and_tokens(served):
    model, tree = served[:2]
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, 128, size=n).astype(np.int32) for n in (5, 12, 20, 9)
    ]
    return prompts, [_greedy(model, tree, p, 6) for p in prompts]


def _engine(served, **kw):
    model, _, _, params = served[:4]
    args = dict(n_slots=2, max_len=48, max_new_tokens=6, chunk=4, pad_id=-1)
    args.update(kw)
    return ContinuousBatcher(tiny.config(model, HELD), params, **args)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_engine_greedy_tokens_are_the_references(
    served, prompts_and_tokens, layout
):
    prompts, want = prompts_and_tokens
    kw = {"kv_layout": layout}
    if layout == "paged":
        kw["page_size"] = 8
    eng = _engine(served, **kw)
    got = eng.generate_all(prompts)
    assert [list(map(int, g)) for g in got] == want
    assert eng.kernel_path == "reference"


def test_engine_through_the_latent_kernel(
    served, prompts_and_tokens, monkeypatch
):
    """Interpret-mode kernels forced into the dispatch: the chunk
    program steps the latent pool page by page through
    `paged_attention_decode_latent`."""
    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    prompts, want = prompts_and_tokens
    eng = _engine(served, kv_layout="paged", page_size=8)
    assert eng.kernel_path == "kernel"
    got = eng.generate_all(prompts)
    assert [list(map(int, g)) for g in got] == want


def test_preempted_requests_replay_to_the_same_tokens(
    served, prompts_and_tokens
):
    """A pool too small for both slots' runs: the colder request is
    swapped out and replayed, and the tokens are the reference's."""
    prompts, want = prompts_and_tokens
    eng = _engine(
        served, n_slots=3, kv_layout="paged", page_size=8, n_pages=7,
        swap_headroom=0)
    got = eng.generate_all(prompts)
    assert [list(map(int, g)) for g in got] == want
    assert eng.paged_stats()["swap_preemptions"] > 0


def test_step_spans_count_latent_rows_and_held_pairs(served):
    """One request of 5 prompt tokens and 6 new ones alone in the
    engine: its six steps read 5 .. 10 rows in each of 3 layers; every
    step routes 4 pairs a slot and expert layer over the 32 experts,
    of which the spans count those on the 8 held here."""
    cfg = tiny.config(served[0], HELD)
    eng = _engine(served, n_slots=1, kv_layout="paged", page_size=8)
    mark = len(trace.snapshot())
    prompt = np.arange(1, 6, dtype=np.int32)
    eng.generate_all([prompt])
    steps = [
        r[trace.COUNTS] for r in trace.snapshot()[mark:]
        if r[trace.NAME] == "engine.step"
    ]
    assert all("pages_latent" in c and "latent_cells" in c for c in steps)
    assert sum(c["latent_cells"] for c in steps) == 3 * sum(range(5, 11))
    routed = [c for c in steps if c.get("moe_routed_pairs")]
    assert routed
    for c in routed:
        assert c["moe_routed_pairs"] == (
            c["moe_steps"] * 1 * cfg.moe_top_k * cfg.n_moe_layers)
        assert c["moe_held_pairs"] == c["moe_pairs"] <= c["moe_routed_pairs"]
        # an expert that got a pair in a layer and step is touched
        # once there: at most a pair's worth, at most every expert
        assert 0 < c["moe_experts_touched"] <= min(
            c["moe_held_pairs"],
            c["moe_steps"] * cfg.n_moe_layers * HELD[1])
        assert c["moe_max_load"] >= c["moe_mean_load"]
    share = eng.paged_stats()["moe_held_pairs_share"]
    assert share == (
        sum(c["moe_held_pairs"] for c in routed)
        / sum(c["moe_routed_pairs"] for c in routed))
    admits = [
        r[trace.COUNTS] for r in trace.snapshot()[mark:]
        if r[trace.NAME] == "engine.admit"
    ]
    assert admits and admits[0]["prompt_tokens"] == 5
    # a held share's rows are bounded for the worst deal: every pair
    # of the bucket on the experts held here
    from dlrover_tpu.models import moe

    assert admits[0]["moe_rows"] == moe.dropless_rows(
        admits[0]["bucket"] * cfg.moe_top_k, HELD[1])


class _Registry:
    """Stands where an adapter registry would: the refusal comes
    before anything reads it."""


@pytest.mark.parametrize(
    "knob",
    [
        {"prefix_cache_rows": 2, "prefix_block": 8},
        {"kv_tier_bytes": 1 << 20},
        {"replica_role": "prefill"},
        {"replica_role": "decode"},
        {"spec_draft_len": 2},
        {"adapter_registry": _Registry()},
        {"weight_quant": "int8"},
        {"mesh_spec": 2},
        {"kv_quant": True},
        {"prefill_chunk": 8},
    ],
    ids=lambda k: next(iter(k)),
)
def test_what_moves_k_and_v_page_runs_is_refused_by_name(served, knob):
    with pytest.raises(ValueError, match="a latent cache"):
        _engine(served, kv_layout="paged", page_size=8, **knob)


def test_resize_is_refused_by_name(served):
    eng = _engine(served, kv_layout="paged", page_size=8)
    with pytest.raises(ValueError, match="a latent cache.*resize"):
        eng.resize(1)


def test_training_refuses_each_new_field_by_name(served):
    cfg = tiny.config(served[0], HELD)
    with pytest.raises(ValueError) as err:
        llama.apply(cfg, served[3], jnp.zeros((1, 8), jnp.int32))
    for name in ("latent attention", "first_k_dense", "n_shared_experts",
                 "moe_scoring='sigmoid'", "experts_held"):
        assert name in str(err.value)


def test_config_refuses_what_it_cannot_mean():
    model = tiny.model_dict()
    with pytest.raises(ValueError, match="experts_held"):
        tiny.config(model, (30, 8))
    with pytest.raises(ValueError, match="moe_n_group"):
        tiny.config(model, HELD, moe_n_group=5)
    with pytest.raises(ValueError, match="first_k_dense"):
        tiny.config(model, HELD, dense_mlp_dim=0)
    with pytest.raises(ValueError, match="latent attention"):
        tiny.config(model, HELD, q_lora_rank=0)
    with pytest.raises(ValueError, match="dropless"):
        tiny.config(model, HELD, moe_routing="capacity")
    cfg = tiny.config(model, HELD)
    assert cfg.head_dim == 24 and cfg.latent_width == 128
    assert cfg.held == HELD and cfg.n_moe_layers == 2
