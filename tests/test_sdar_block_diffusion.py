"""SDAR's mechanisms against the plain reference
(tests/reference_models/sdar.py) at a test's size, float32, seeded
weights: per-head norms of q and k, the block mask (causal across
blocks of 4 positions, two-sided inside one) in the prefill and over
the paged pool, and generation by diffusion over blocks through
`ContinuousBatcher`: a slot's state is a block, a forward unmasks some
of it and carries the block finished before it for its keys and
values, and a forward no longer yields one token. The served loop is
held to the published one (T denoising forwards and a commit a block)
id for id and cell for cell."""

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


def _installed_pool(cfg, params, prompt, max_len):
    """(pool, table) of one slot whose prompt the block-masked prefill
    installed."""
    pool = decode.init_page_pool(cfg, max_len // PAGE + 1, PAGE)
    table_row = jnp.arange(1, max_len // PAGE + 1, dtype=jnp.int32)
    padded = jnp.asarray(prompt + [0] * (16 - len(prompt)), jnp.int32)
    row = decode.prefill_exact_row(cfg, params, padded, max_len)
    return decode.paged_install_row(pool, row, table_row, 0, 16), (
        table_row[None])


@pytest.fixture(scope="module")
def forwards_of(sdar):
    """(impl) -> (one block a slot, the published loop's forward;
    two blocks a slot, the served loop's), jitted once a module."""
    _, cfg, params = sdar
    made = {}

    def make(impl):
        if impl not in made:
            c = dataclasses.replace(cfg, attn_impl=impl)
            offs = jnp.arange(B, dtype=jnp.int32)

            @jax.jit
            def plain(pool, table, start, ids):
                logits, pool, _ = decode._forward_paged(
                    c, params, ids[None], pool, table, start + offs[None])
                return logits[0], pool

            @jax.jit
            def fused(pool, table, start, prev, ids, carried):
                both = jnp.concatenate([offs - B, offs])
                logits, pool, _ = decode._forward_paged(
                    c, params, jnp.concatenate([prev, ids])[None], pool,
                    table, jnp.maximum(start + both, 0)[None],
                    carried=carried[None],
                )
                return logits[0], pool

            made[impl] = plain, fused
        return made[impl]

    return make


def _cells(pool, lo, hi):
    """Positions lo .. hi - 1 of the one slot `_installed_pool` lays
    out (logical page i is physical page i + 1), every layer."""
    return {
        n: np.asarray(arr[:, 1:]).reshape(
            (arr.shape[0], -1) + arr.shape[3:])[:, lo:hi]
        for n, arr in pool.items()
    }


@pytest.mark.parametrize("impl", ["reference", "kernel"])
@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("p", [8, 9, 10, 11])
def test_fused_forwards_are_the_published_loops(
    sdar, forwards_of, monkeypatch, steps, p, impl
):
    """A prompt with each p % 4 (its first block carries nothing: the
    prefill installed every earlier cell), then every denoising state
    of every block as ONE forward over the paged pool that carries
    the block finished before it: the block's logits are the ones the
    reference computes by re-running the whole sequence, and once the
    slot has moved on the finished block's cells are the ones the
    published loop's commit forward stores. A forward that carries
    nothing changes no cell but its own block's. Through the gathered
    view and through the kernel (interpreted here)."""
    model, cfg, params = sdar
    if impl == "kernel":
        monkeypatch.setattr(fa, "force_kernels", lambda: True)
    plain, fused = forwards_of("auto" if impl == "kernel" else impl)
    prompt = _prompt(p, seed=p)
    forwards = []
    _generate(model, params, prompt, 9, steps, forwards)
    max_len = 32
    pool, table = _installed_pool(cfg, params, prompt, max_len)
    published = pool
    prev = None                # the finished block, owed to the pool
    for i, (start, ids, taken, toks, want) in enumerate(forwards):
        ids_in = jnp.asarray(ids, jnp.int32)
        _, published = plain(published, table, start, ids_in)
        was = _cells(pool, 0, max_len)
        got, pool = fused(
            pool, table, start,
            jnp.zeros(B, jnp.int32) if prev is None else prev, ids_in,
            jnp.asarray(prev is not None),
        )
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        now = _cells(pool, 0, max_len)
        for n in now:
            if prev is None:
                # dead carried rows: nothing before the block moved
                np.testing.assert_array_equal(
                    now[n][:, :start], was[n][:, :start])
            else:
                # moved on: the commit forward's cells
                np.testing.assert_allclose(
                    now[n][:, start - B:start],
                    _cells(published, start - B, start)[n],
                    atol=2e-6, rtol=2e-5,
                )
            np.testing.assert_array_equal(
                now[n][:, start + B:], was[n][:, start + B:])
        prev = None
        if i + 1 == len(forwards) or forwards[i + 1][0] != start:
            final = list(ids)
            for j, t in zip(taken, toks):
                final[j] = t
            prev = jnp.asarray(final, jnp.int32)
            # the published loop's commit: the block once more
            _, published = plain(published, table, start, prev)


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


@pytest.mark.parametrize("carried", [False, True], ids=["block", "carried"])
@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_block_paged_call_against_the_einsum(impl, carried):
    """`paged_attention(..., block=4)`: four queries a slot, one
    length a slot (the block's end), as 4 x H heads through the one
    walk (interpreted here) and through the gathered view, against
    the einsum formulation over each slot's own cells. With the block
    before it CARRIED, eight queries a slot in the same call: the
    carried block's see the cells up to their own block's end, a
    second group of rows one block shorter."""
    rng = np.random.default_rng(1)
    slots, h, kv, hd, layers, pages = 3, 4, 2, 128, 2, 4
    pool = {
        n: jnp.asarray(rng.standard_normal(
            (layers, slots * pages + 1, PAGE, kv, hd)), jnp.float32)
        for n in ("k", "v")
    }
    table = jnp.asarray(
        1 + rng.permutation(slots * pages).reshape(slots, pages), jnp.int32)
    # a block at a page's first cells and one at its last, so that
    # the carried rows' last cells lie on the page before
    starts = jnp.asarray([4, 16, 28] if carried else [0, 12, 28], jnp.int32)
    s = 2 * B if carried else B
    q = jnp.asarray(rng.standard_normal((slots, s, h, hd)), jnp.float32)
    got = pa.paged_attention(
        q, pool, table, starts + B, impl=impl, layer=1, block=B)
    view = pa.gather_pages(pool, table, 1)
    for i in range(slots):
        end = int(starts[i]) + B
        # the queries stand at their blocks' positions, so each sees
        # every cell up to its own block's end
        want = _einsum_block_attention(
            jnp.concatenate([jnp.zeros((end - s, h, hd)), q[i]]),
            view["k"][i, :end], view["v"][i, :end], B,
        )[-s:]
        np.testing.assert_allclose(got[i], want, atol=2e-5, rtol=2e-5)
    rows = pa.block_rows(q, kv)
    assert rows.shape == (slots, s * h, hd)
    np.testing.assert_array_equal(pa._block_rows_back(rows, s, kv), q)
    with pytest.raises(ValueError, match="with a carried block"):
        pa.paged_attention(
            q[:, :3], pool, table, starts + B, impl=impl, layer=1, block=B)


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
    """A request swapped out part-way through a block (4 steps), or
    just after one whose keys and values the pool is still owed (2
    steps at 2 forwards a dispatch): its emitted tokens fold into its
    prompt, whole blocks, so the replay's first block starts where
    the lost one did and the prefill installs what was owed. It still
    streams the reference's ids, and so does its neighbour."""
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


def _blocks_read(rows):
    """A copy of the rule `perfbench/reference_sdar.trajectory_states`
    reads a recorded trajectory by: every block is its phase-1 rows
    (a forward each: the ids it unmasked, -1 elsewhere) and then ONE
    phase-2 row, the block's ids; a block begun again after a
    preemption keeps the rows of its last run -> [(start, [positions
    a forward unmasked], ids)]."""
    blocks, pending = [], []
    for start, phase, ids in rows:
        if phase == 1:
            pending.append((start, ids))
            continue
        assert phase == 2
        mine, covered = [], set()
        for s0, took in reversed(pending):
            idx = [j for j, t in enumerate(took) if t >= 0]
            if s0 != start or covered & set(idx):
                continue
            assert [took[j] for j in idx] == [ids[j] for j in idx]
            mine.insert(0, idx)
            covered |= set(idx)
        blocks.append((start, mine, ids))
        pending = [x for x in pending if x[0] != start]
    assert not pending, "denoising rows with no phase-2 row after them"
    return blocks


@pytest.mark.parametrize("preempt", [False, True], ids=["whole", "replayed"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_block_trajectories_read_as_the_published_loops(
    sdar, steps, preempt
):
    """`record_blocks`: a request's rows, from which the benchmark's
    check rebuilds each denoising state, read by its rule as the
    reference's own trace, forward for forward, whichever forward
    stored a block's keys and values and whether or not the request
    was swapped out on the way."""
    model, cfg, params = sdar
    eng = _engine(cfg, params, denoising_steps=steps, chunk=2)
    eng.record_blocks = True
    prompt = _prompt(7, seed=41)
    idx = eng.submit(prompt, max_new=10)
    if preempt:
        # two forwards in: a block owed to the pool (1 step), a block
        # begun and abandoned (2, 4)
        eng.step()
        eng._preempt_slot(0)
    eng.generate_all([])
    forwards = []
    _generate(model, params, prompt, 10, steps, forwards)
    blocks = _blocks_read(eng.block_trajectories()[idx])
    assert [start for start, _, _ in blocks] == [4, 8, 12, 16]
    want = {}
    for start, _ids, taken, _toks, _l in forwards:
        want.setdefault(start, []).append(taken)
    assert {start: mine for start, mine, _ in blocks} == want
    stream = [t for _, _, ids in blocks for t in ids]
    assert stream[7 - 4:][:10] == _generate(model, params, prompt, 10, steps)
    assert stream[:7 - 4] == prompt[4:]


@pytest.mark.parametrize("steps,p,n,forwards,fused", [
    (2, 8, 16, 8, 3),      # whole blocks: 2 ids a forward
    (2, 9, 9, 6, 2),       # 1 id of the first block given
    (4, 10, 6, 6, 1),      # 2 given: 2 forwards, then 4
    (1, 11, 13, 4, 3),     # a block a forward
    (2, 8, 2, 2, 0),       # one block, cut by its limit
])
def test_forwards_left_is_what_a_request_takes(
    sdar, steps, p, n, forwards, fused
):
    """T forwards a block and none to commit it: `_forwards_left` at
    admission is the forwards the request then takes (the published
    loop's less its commits), each dispatch takes its length off it,
    the last block's ids are handed over with no further forward, and
    the step's counters say so: `diff_tokens / diff_forwards` is 2.0
    on whole blocks at 2 steps, `diff_fused` counts the forwards that
    carried a finished block."""
    model, cfg, params = sdar
    trace.clear()
    eng = _engine(cfg, params, denoising_steps=steps, n_slots=1)
    published = []
    want = _generate(model, params, _prompt(p, seed=p), n, steps, published)
    assert forwards == len(published)
    left = []
    pick = eng._next_chunk_len

    def watched():
        left.append(int(eng._forwards_left()[0]))
        return pick()

    eng._next_chunk_len = watched
    eng.submit(_prompt(p, seed=p), max_new=n)
    assert eng.generate_all([])[0].tolist() == want
    steps_ = [
        r[trace.COUNTS] for r in trace.snapshot()
        if r[trace.NAME] == "engine.step"
        and "diff_forwards" in r[trace.COUNTS]
    ]
    took = [c["diff_forwards"] for c in steps_]
    assert left[0] == forwards == sum(took)
    assert left == [forwards - sum(took[:i]) for i in range(len(took))]
    assert sum(c["diff_fused"] for c in steps_) == fused
    assert sum(c["diff_tokens"] for c in steps_) == n
    assert sum(c["diff_commits"] for c in steps_) == -(-(p + n) // B) - p // B
    assert eng.paged_stats()["diffusion_tokens_per_forward"] == (
        pytest.approx(n / forwards))


@pytest.mark.parametrize("lag", [1, 2, 3])
def test_a_slot_admitted_beside_slots_at_another_phase(sdar, lag):
    """One forward a dispatch, a second request admitted `lag`
    forwards after the first: in one forward a slot carries a block
    and its neighbour does not (a block's later forward, a request's
    first block). Both stream the reference's ids."""
    model, cfg, params = sdar
    trace.clear()
    eng = _engine(cfg, params, denoising_steps=2, chunk=1, n_slots=2)
    prompts = [_prompt(8, seed=61), _prompt(8, seed=62)]
    eng.submit(prompts[0], max_new=12)
    for _ in range(lag):
        eng.step()
    eng.submit(prompts[1], max_new=12)
    outs = eng.generate_all([])
    for prompt, out in zip(prompts, outs):
        assert out.tolist() == _generate(model, params, prompt, 12, 2)
    steps_ = [
        r[trace.COUNTS] for r in trace.snapshot()
        if r[trace.NAME] == "engine.step"
        and "diff_forwards" in r[trace.COUNTS]
    ]
    both = [c for c in steps_ if c["diff_forwards"] == 2]
    mixed = [c for c in both if c["diff_fused"] == 1]
    # the newcomer's first block carries nothing; after it, at an odd
    # lag one of the two carries whenever the other does not
    assert len(mixed) == (len(both) - 1 if lag % 2 else 1)


def test_done_and_dead_rows_change_no_live_cell(sdar):
    """One forward of the scan over three slots: a done row (its
    table is routed to the trash page), a live slot that carries
    nothing (dead carried rows) and a live slot that carries a block.
    No cell changes but the trash page's, the second slot's block's
    and the third's two blocks'."""
    from dlrover_tpu.serving.engine import _diffusion_scan

    _, cfg, params = sdar
    rng = np.random.default_rng(7)
    pages = 4
    pool = {
        n: jnp.asarray(rng.standard_normal(arr.shape), jnp.float32)
        for n, arr in decode.init_page_pool(cfg, 3 * pages + 1, PAGE).items()
    }
    table = jnp.arange(1, 3 * pages + 1, dtype=jnp.int32).reshape(3, pages)
    mask = tiny.mask_id(tiny.model_dict())
    blk = jnp.full((3, B), mask, jnp.int32)
    msk = jnp.ones((3, B), bool)
    prev = jnp.asarray([[-1] * B, [-1] * B, [5, 6, 7, 8]], jnp.int32)
    pos = jnp.asarray([8, 12, 20], jnp.int32)
    done = jnp.asarray([True, False, False])
    limit = jnp.asarray([12, 32, 32], jnp.int32)
    new_pool, *_rest, phase, fused, _pairs = _diffusion_scan(
        cfg, 2, pool, params, blk, msk, prev, pos, done, limit, 1, table)
    assert phase[:, 0].tolist() == [0, 1, 1]
    assert fused[:, 0].tolist() == [False, False, True]
    for n in pool:
        was = np.asarray(pool[n][:, 1:]).reshape(
            (cfg.n_layers, 3, pages * PAGE) + pool[n].shape[3:])
        now = np.asarray(new_pool[n][:, 1:]).reshape(was.shape)
        changed = (was != now).any(axis=(0, 3, 4))
        want = np.zeros_like(changed)
        want[1, 12:16] = True
        want[2, 16:24] = True
        np.testing.assert_array_equal(changed, want)


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
    position: the positions its finished blocks (and its prompt's
    whole blocks) hold, whatever the forwards spent."""
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
            # prompt + emitted tokens, down to a block boundary
            assert progress == (6 + len(req.out)) // B * B
            assert progress == eng._slot_progress(0)
            seen.append(progress)
    # the first block (2 of its positions given) is finished by the
    # first dispatch of 2 forwards
    assert seen[0] == 8 and sorted(set(seen)) == [8, 12, 16]
    steps = [r for r in trace.snapshot() if r[trace.NAME] == "engine.step"]
    live = [r[trace.COUNTS]["live_tokens"] for r in steps]
    assert set(live) <= {0, 8, 12, 16}
    # 2 forwards, then 3 blocks of 4
    forwards = sum(r[trace.COUNTS].get("diff_forwards", 0) for r in steps)
    assert forwards == 2 + 3 * 4


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
    # 8 forwards at 2 a dispatch: half the pumps delivered nothing
    assert pumps >= 4
    # (last - first) / (ids - 1): the second block came 2 pumps after
    # the first (4 forwards at 2 a dispatch)
    assert metrics._tpot_ms.quantiles()[0.5] == pytest.approx(
        2000.0 / 7, rel=0.35)
    assert eng.paged_stats()["diffusion_tokens_per_forward"] == (
        pytest.approx(8 / 8)
    )
    assert "serving_diffusion_tokens_per_forward 1" in (
        metrics.render()
    )
