"""SDAR's mechanisms against the plain reference
(tests/reference_models/sdar.py) at a test's size, float32, seeded
weights: per-head norms of q and k, the block mask (causal across
blocks of 4 positions, two-sided inside one) in the prefill and over
the paged pool, and generation by diffusion over blocks through
`ContinuousBatcher`: a slot's state is a block, a forward unmasks some
of it or commits it, and a forward no longer yields one token."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _sdar_tiny as tiny  # noqa: E402
from reference_models import sdar as ref  # noqa: E402

from dlrover_tpu.common import trace  # noqa: E402
from dlrover_tpu.models import decode, llama  # noqa: E402
from dlrover_tpu.ops import attention as attn_ops  # noqa: E402
from dlrover_tpu.ops import flash_attention as fa  # noqa: E402
from dlrover_tpu.ops import paged_attention as pa  # noqa: E402
from dlrover_tpu.serving.engine import ContinuousBatcher  # noqa: E402
from dlrover_tpu.serving.metrics import ServingMetrics  # noqa: E402
from dlrover_tpu.serving.scheduler import (  # noqa: E402
    RequestScheduler,
    SloConfig,
)

B = tiny.BLOCK
PAGE = 8


@pytest.fixture(scope="module")
def sdar():
    model = tiny.model_dict()
    return model, tiny.config(model), tiny.params(model)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 126, size=n).tolist()


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 16)
    kw.setdefault("chunk", 4)
    kw.setdefault("pad_id", -1)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", PAGE)
    kw.setdefault("async_depth", 0)
    return ContinuousBatcher(cfg, params, **kw)


def _generate(model, params, prompt, n, steps, trace_=None):
    return ref.block_diffusion_generate(
        model, params, prompt, n, B, steps, tiny.mask_id(model), trace_
    )


# ---- the forward: norms, block mask, paged pool ----------------------------


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("p", [8, 9, 10, 11])
def test_paged_block_forwards_give_the_references_logits(sdar, steps, p):
    """A prompt with each p % 4: the block-masked prefill installed
    into the pages, then every denoising state of every block as ONE
    forward of 4 positions over the paged pool, gives the logits the
    reference computes by re-running the whole sequence; the commit's
    forward stores what the next block reads."""
    model, cfg, params = sdar
    prompt = _prompt(p, seed=p)
    forwards = []
    _generate(model, params, prompt, 9, steps, forwards)
    max_len = 32
    pool = decode.init_page_pool(cfg, max_len // PAGE + 1, PAGE)
    table_row = jnp.arange(1, max_len // PAGE + 1, dtype=jnp.int32)
    padded = jnp.asarray(prompt + [0] * (16 - p), jnp.int32)
    row = decode.prefill_exact_row(cfg, params, padded, max_len)
    pool = decode.paged_install_row(pool, row, table_row, 0, 16)
    table = table_row[None]

    def run(start, ids):
        positions = start + jnp.arange(B, dtype=jnp.int32)[None]
        logits, new_pool, _counts = decode._forward_paged(
            cfg, params, jnp.asarray([ids], jnp.int32), pool, table, positions
        )
        return logits[0], new_pool

    for i, (start, ids, taken, toks, want) in enumerate(forwards):
        got, pool = run(start, ids)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        last = i + 1 == len(forwards) or forwards[i + 1][0] != start
        if last:  # the commit: the block's final ids, stored
            final = list(ids)
            for j, t in zip(taken, toks):
                final[j] = t
            _, pool = run(start, final)


def test_qk_norm_is_before_the_rotary_turn_and_is_the_references(sdar):
    """One forward under the block mask against the reference's, and
    the norms matter: with scales of 1 in their place it differs."""
    model, cfg, params = sdar
    ids = _prompt(16, seed=3)
    want = ref.forward(model, params, ids, B)
    cache = decode.init_kv_cache(cfg, 1, 16)
    got, _ = decode._forward_cached(
        cfg, params, jnp.asarray([ids]), cache,
        jnp.arange(16)[None], 0, plain_causal=True,
    )
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)
    ones = jax.tree_util.tree_map(lambda x: x, params)
    ones["layers"] = dict(
        params["layers"], q_norm=jnp.ones_like(params["layers"]["q_norm"])
    )
    other, _ = decode._forward_cached(
        cfg, ones, jnp.asarray([ids]), cache,
        jnp.arange(16)[None], 0, plain_causal=True,
    )
    assert float(jnp.abs(other[0] - want).max()) > 1e-3
    # and the block mask is not the causal one
    causal = dataclasses.replace(cfg, block_length=0)
    other, _ = decode._forward_cached(
        causal, params, jnp.asarray([ids]), cache,
        jnp.arange(16)[None], 0, plain_causal=True,
    )
    assert float(jnp.abs(other[0] - want).max()) > 1e-3


def _einsum_block_attention(q, k, v, block):
    """[S, H, hd] x [M, KV, hd]: query i (at position i) sees key j
    iff j // block <= i // block."""
    s, h, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(s, kv, h // kv, hd)
    scores = jnp.einsum("qkgd,skd->kgqs", qg, k) / np.sqrt(hd)
    seen = ref.block_mask(jnp.arange(k.shape[0]), block)[:s]
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), -1)
    return jnp.einsum("kgqs,skd->qkgd", probs, v).reshape(s, h, hd)


def test_flash_forward_block_mask_in_interpret_mode():
    """The flash forward kernel with `block=4` (interpreted here)
    against the einsum formulation: tiles of 128 x 128, so rows of a
    block see keys past the diagonal inside their tile only."""
    rng = np.random.default_rng(0)
    s, h, kv, hd = 256, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((1, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, kv, hd)), jnp.float32)
    got = fa.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, block=B)
    want = _einsum_block_attention(q[0], k[0], v[0], B)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)
    plain = attn_ops.reference_attention(q, k, v, causal=True, block=B)
    np.testing.assert_allclose(plain[0], want, atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="power of two"):
        fa.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, block=3)
    with pytest.raises(ValueError, match="block mask"):
        attn_ops.dot_product_attention(q, k, v, causal=False, block=B)


@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_block_paged_call_against_the_einsum(impl):
    """`paged_attention(..., block=4)`: four queries a slot, one
    length a slot (the block's end), as 4 x H heads through the one
    walk (interpreted here) and through the gathered view, against
    the einsum formulation over each slot's own cells."""
    rng = np.random.default_rng(1)
    slots, h, kv, hd, layers, pages = 3, 4, 2, 128, 2, 4
    pool = {
        n: jnp.asarray(rng.standard_normal(
            (layers, slots * pages + 1, PAGE, kv, hd)), jnp.float32)
        for n in ("k", "v")
    }
    table = jnp.asarray(
        1 + rng.permutation(slots * pages).reshape(slots, pages), jnp.int32)
    starts = jnp.asarray([0, 12, 28], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, B, h, hd)), jnp.float32)
    got = pa.paged_attention(
        q, pool, table, starts + B, impl=impl, layer=1, block=B)
    view = pa.gather_pages(pool, table, 1)
    for s in range(slots):
        end = int(starts[s]) + B
        # the block's queries stand at its positions: inside one
        # block, so they see every cell up to its end
        want = _einsum_block_attention(
            jnp.concatenate(
                [jnp.zeros((int(starts[s]), h, hd)), q[s]]),
            view["k"][s, :end], view["v"][s, :end], B,
        )[-B:]
        np.testing.assert_allclose(got[s], want, atol=2e-5, rtol=2e-5)
    rows = pa.block_rows(q, kv)
    assert rows.shape == (slots, B * h, hd)
    np.testing.assert_array_equal(pa._block_rows_back(rows, B, kv), q)


# ---- the engine: streams id for id -----------------------------------------


@pytest.mark.parametrize("depth", [0, 1])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_engine_streams_are_the_references(sdar, steps, depth):
    """Five requests over three slots (so slots stand at different
    phases of different blocks, and a slot is refilled mid-run),
    prompts with every p % 4, limits that end mid-block: each stream
    is `block_diffusion_generate`'s, id for id."""
    model, cfg, params = sdar
    eng = _engine(cfg, params, denoising_steps=steps, async_depth=depth)
    sizes = [(5, 9), (8, 12), (10, 6), (7, 16), (3, 5)]
    prompts = [_prompt(p, seed=10 + i) for i, (p, _) in enumerate(sizes)]
    for prompt, (_, n) in zip(prompts, sizes):
        eng.submit(prompt, max_new=n)
    outs = eng.generate_all([])
    for prompt, (_, n), out in zip(prompts, sizes, outs):
        assert out.tolist() == _generate(model, params, prompt, n, steps)
    assert eng.allocator.used_pages == 0


@pytest.mark.parametrize("steps", [2, 4])
def test_a_preempted_request_replays_onto_the_same_blocks(sdar, steps):
    """A request swapped out part-way through a block (its committed
    tokens fold into its prompt: whole blocks, so the replay's first
    block starts where the lost one did) still streams the
    reference's ids, and so does its neighbour."""
    model, cfg, params = sdar
    eng = _engine(cfg, params, denoising_steps=steps, chunk=2)
    prompts = [_prompt(6, seed=21), _prompt(9, seed=22)]
    first = eng.submit(prompts[0], max_new=14)
    eng.submit(prompts[1], max_new=11)
    for _ in range(3):
        eng.step()
    slot = next(
        s for s in range(eng.n_slots)
        if eng.slot_req[s] is not None and eng.slot_req[s].idx == first
    )
    assert 0 < len(eng.slot_req[slot].out) < 14
    eng._preempt_slot(slot)
    replay = eng._queue[0]
    assert len(replay.prompt) % B == 0 and replay.preempted
    outs = eng.generate_all([])
    for prompt, n, out in zip(prompts, (14, 11), outs):
        assert out.tolist() == _generate(model, params, prompt, n, steps)
    assert eng.paged_stats()["swap_preemptions"] == 1.0


def test_block_trajectories_rebuild_every_forward(sdar):
    """`record_blocks`: a request's forwards in order, from which the
    benchmark's check rebuilds each denoising state: the reference's
    own trace, forward for forward."""
    model, cfg, params = sdar
    eng = _engine(cfg, params, denoising_steps=2)
    eng.record_blocks = True
    prompt = _prompt(7, seed=41)
    idx = eng.submit(prompt, max_new=10)
    eng.generate_all([])
    forwards = []
    _generate(model, params, prompt, 10, 2, forwards)
    rows = eng.block_trajectories()[idx]
    denoise = [r for r in rows if r[1] == 1]
    assert len(denoise) == len(forwards)
    for (start, _phase, ids), (want_start, _ids, taken, toks, _l) in zip(
        denoise, forwards
    ):
        assert start == want_start
        assert [j for j, t in enumerate(ids) if t >= 0] == taken
        assert [t for t in ids if t >= 0] == toks
    commits = [r for r in rows if r[1] == 2]
    assert [r[0] for r in commits] == [4, 8, 12, 16]
    stream = [t for r in commits for t in r[2]]
    assert stream[7 - 4:][:10] == _generate(model, params, prompt, 10, 2)


# ---- what is refused, by name ----------------------------------------------


@pytest.mark.parametrize("knob", [
    {"prefix_cache_rows": 2},
    {"kv_tier_bytes": 1 << 20},
    {"replica_role": "prefill"},
    {"spec_draft_len": 2},
    {"prefill_chunk": 16},
    {"weight_quant": "int8"},
    {"kv_quant": True},
    {"mesh_spec": 2},
    {"adapter_registry": object()},
], ids=lambda k: next(iter(k)))
def test_the_engine_refuses_what_it_does_not_serve_with_blocks(sdar, knob):
    _, cfg, params = sdar
    with pytest.raises(ValueError, match="diffusion over blocks"):
        _engine(cfg, params, **knob)


@pytest.mark.parametrize("knob,said", [
    ({"kv_layout": "dense", "page_size": 0}, "kv_layout='dense'"),
    ({"temperature": 0.7}, "temperature > 0"),
    ({"eos_id": 5}, "eos_id"),
    ({"denoising_steps": 5}, "denoising_steps outside"),
    ({"max_len": 66, "page_size": 2}, "max_len not a multiple"),
    ({"page_size": 2}, "never straddles a page"),
])
def test_the_engine_names_how_blocks_are_served(sdar, knob, said):
    _, cfg, params = sdar
    with pytest.raises(ValueError, match=said):
        _engine(cfg, params, **knob)


def test_denoising_steps_are_a_block_models(sdar):
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="block-diffusion model's"):
        ContinuousBatcher(cfg, params, denoising_steps=2)


@pytest.mark.parametrize("field,said", [
    ({"qk_norm": True}, r"normalised q and k \(qk_norm\)"),
    ({"block_length": 4, "mask_token_id": 7}, "diffusion over blocks"),
])
def test_training_refuses_the_mechanism_by_name(field, said):
    cfg = llama.LlamaConfig.tiny(**field)
    with pytest.raises(ValueError, match=said):
        llama.refuse_training(cfg)
    with pytest.raises(ValueError, match=said):
        llama.apply(
            cfg, llama.init_params(cfg, jax.random.PRNGKey(0)),
            jnp.zeros((1, 8), jnp.int32),
        )


@pytest.mark.parametrize("over", [
    {"block_length": 3}, {"block_length": 1},
    {"block_length": 4, "mask_token_id": 256},
    {"block_length": 4, "layer_pattern": ("window", "full"),
     "sliding_window": 8},
])
def test_the_config_refuses_a_block_it_cannot_mask(over):
    with pytest.raises(ValueError):
        llama.LlamaConfig.tiny(**over)


def test_init_params_brings_the_norms_only_where_asked():
    plain = llama.init_params(llama.LlamaConfig.tiny(), jax.random.PRNGKey(0))
    assert "q_norm" not in plain["layers"]
    cfg = llama.LlamaConfig.tiny(qk_norm=True)
    normed = llama.init_params(cfg, jax.random.PRNGKey(0))
    assert normed["layers"]["q_norm"].shape == (2, cfg.head_dim)
    assert normed["layers"]["k_norm"].shape == (2, cfg.head_dim)


# ---- the host's accounting: stored positions, not forwards ----------------


def test_pages_cover_the_end_of_the_last_block(sdar):
    """A request of 9 prompt and 6 new tokens ends at position 14,
    inside the block 12..15: its run covers cell 15. One more token
    and the limit is a page's first cell's block."""
    _, cfg, params = sdar
    eng = _engine(cfg, params, denoising_steps=2)
    eng.submit(_prompt(9, seed=1), max_new=6)       # limit 15 -> end 16
    eng.submit(_prompt(9, seed=1), max_new=8)       # limit 17 -> end 20
    reqs = list(eng._queue)
    assert [eng._request_pages(r) for r in reqs] == [2, 3]
    eng.generate_all([])


def test_other_models_pages_read_as_before():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatcher(
        cfg, params, n_slots=2, max_len=64, kv_layout="paged",
        page_size=PAGE, pad_id=-1,
    )
    for p, n, want in ((9, 6, 2), (9, 7, 2), (9, 8, 3), (40, 64, 8)):
        eng.submit(_prompt(p, seed=p), max_new=n)
        assert eng._request_pages(eng._queue[-1]) == want
    assert eng.blk.shape == (2, 0) and "blk" not in eng._dev


def test_progress_and_live_tokens_count_stored_positions(sdar):
    """After every step a live slot's `pos` is its block's first
    position: the K/V cells its committed blocks (and its prompt's
    whole blocks) fill, whatever the forwards spent."""
    _, cfg, params = sdar
    trace.clear()
    eng = _engine(cfg, params, denoising_steps=4, chunk=2, n_slots=1)
    idx = eng.submit(_prompt(6, seed=5), max_new=12)
    seen = []
    while eng.has_work():
        eng.step()
        progress = eng.request_progress(idx)
        if progress is not None:
            req = eng._requests[idx]
            # prompt + committed tokens, down to a block boundary
            assert progress == (6 + len(req.out)) // B * B
            assert progress == eng._slot_progress(0)
            seen.append(progress)
    assert seen[0] == 4 and sorted(set(seen)) == [4, 8, 12, 16]
    steps = [r for r in trace.snapshot() if r[trace.NAME] == "engine.step"]
    live = [r[trace.COUNTS]["live_tokens"] for r in steps]
    assert set(live) <= {0, 4, 8, 12, 16}
    # 4 blocks of 5 forwards (the first: 2 of its positions given)
    forwards = sum(r[trace.COUNTS].get("diff_forwards", 0) for r in steps)
    assert forwards == 3 + 3 * 5


def test_pump_hands_a_stream_whole_blocks_and_no_empty_event(sdar):
    """The scheduler delivers a block's ids in order, wakes no stream
    for a dispatch that committed nothing, and keeps the time per
    token per ID."""
    model, cfg, params = sdar
    now = [0.0]
    metrics = ServingMetrics()
    eng = _engine(cfg, params, denoising_steps=4, chunk=2, n_slots=1)
    sched = RequestScheduler(
        eng, SloConfig(max_new_tokens=16), metrics=metrics,
        clock=lambda: now[0],
    )
    prompt = _prompt(8, seed=6)
    req = sched.submit(prompt, max_new=8)
    batches = []
    pumps = 0
    while req.state.name not in ("DONE", "FAILED"):
        now[0] += 1.0
        sched.pump()
        pumps += 1
        while not req.stream.empty():
            item = req.stream.get_nowait()
            if item:
                batches.append(list(item))
    want = _generate(model, params, prompt, 8, 4)
    assert [t for b in batches for t in b] == want == req.tokens
    assert [len(b) for b in batches] == [4, 4]
    # 10 forwards at 2 a dispatch: most pumps delivered nothing
    assert pumps >= 5
    # (last - first) / (ids - 1): the second block came 3 pumps after
    # the first (5 forwards at 2 a dispatch)
    assert metrics._tpot_ms.quantiles()[0.5] == pytest.approx(
        3000.0 / 7, rel=0.35)
    assert eng.paged_stats()["diffusion_tokens_per_forward"] == (
        pytest.approx(8 / 10)
    )
    assert "serving_diffusion_tokens_per_forward 0.8" in (
        metrics.render()
    )
