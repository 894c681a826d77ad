"""A model that mixes window and full attention layers and routes its
experts without dropping (Mellum2's block), served through the hybrid
paged cache, against the plain float32 reference
(tests/reference_models/mellum2.py) on seeded random weights at a
test's size."""

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _mellum2_tiny as tiny
from dlrover_tpu.models import decode, llama
from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops import paged_attention as pa
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.paged_kv import PageAllocator, WindowRings
from reference_models import mellum2 as ref

PAGE, WINDOW = 4, 8


@pytest.fixture(scope="module", autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def small():
    model = tiny.model_dict(n_layers=8, window=WINDOW)
    return model, tiny.config(model), tiny.params(model, seed=3)


def _paged_logits(cfg, params, seq, prompt_len, max_len=64, round_cache=None):
    """Prefill `seq[:prompt_len]` into both classes of pages, then
    decode the rest of `seq` one position at a time (teacher forced);
    returns the logits of every decoded position [n, V]."""
    n_slots = 2  # the sequence lives in slot 1; slot 0 stays on trash
    full = PageAllocator(n_slots * (max_len // PAGE) + 1, PAGE)
    rings = WindowRings(
        PageAllocator(64, PAGE), n_slots, cfg.sliding_window, chunk=1)
    pools = decode.init_hybrid_pools(cfg, full.n_pages, 64, PAGE)
    table = np.zeros((n_slots, max_len // PAGE), np.int32)
    run = full.alloc(-(-len(seq) // PAGE))
    table[1, : len(run)] = run
    bucket = 16
    while bucket < prompt_len:
        bucket *= 2
    padded = np.zeros(bucket, np.int32)
    padded[:prompt_len] = seq[:prompt_len]
    rings.hold(1, max(prompt_len - cfg.sliding_window, 0), prompt_len - 1)
    row = decode.prefill_exact_row(cfg, params, jnp.asarray(padded), max_len)
    pools = decode.paged_install_hybrid(
        cfg, pools, row, jnp.asarray(table[1]), jnp.asarray(rings.table[1]),
        prompt_len, bucket,
    )
    if round_cache is not None:
        pools = jax.tree_util.tree_map(
            lambda a: a.astype(round_cache).astype(a.dtype), pools)
    step = jax.jit(
        lambda tok, pools, table, pos, ring: decode.paged_decode_step(
            cfg, params, tok, pools, table, pos, table_win=ring)
    )
    out = []
    for pos in range(prompt_len - 1, len(seq) - 1):
        rings.hold(1, max(pos - cfg.sliding_window + 1, 0), pos)
        rings.check(1, max(pos - cfg.sliding_window + 1, 0))
        tok = jnp.asarray([0, seq[pos]], jnp.int32)
        logits, pools, counts = step(
            tok, pools, jnp.asarray(table),
            jnp.asarray([0, pos], jnp.int32), jnp.asarray(rings.table),
        )
        assert int(counts.sum()) == 2 * cfg.moe_top_k * cfg.n_layers
        out.append(logits[1])
    return jnp.stack(out), rings


# The tolerance: float32 throughout, products at `highest`. What is
# left between the paths is the order of float32 sums (online softmax
# over pages against one softmax, a gather-and-sum combine against a
# loop over experts): 1e-5 of the logits' scale here. A cache rounded
# to bfloat16 moves the logits by 1e-2 of it.
LOGIT_TOL = 2e-4


@pytest.mark.parametrize("prompt_len,total", [(5, 30), (19, 56), (33, 60)])
def test_prefill_then_paged_decode_agrees_with_reference_in_logits(
    small, prompt_len, total
):
    model, cfg, params = small
    seq = np.random.RandomState(total).randint(1, 128, size=total)
    want = ref.forward(model, params, jnp.asarray([seq]))[0]
    got, rings = _paged_logits(cfg, params, seq, prompt_len)
    scale = float(jnp.abs(want).max())
    gap = float(jnp.abs(got - want[prompt_len - 1: total - 1]).max())
    assert gap < LOGIT_TOL * scale, (gap, scale)
    if total > 50:
        # every context crossed the window; the ring went round at
        # least twice
        assert rings.pages_freed_behind >= 2 * rings.ring_pages


def test_a_bf16_cache_fails_the_float32_tolerance(small):
    model, cfg, params = small
    seq = np.random.RandomState(7).randint(1, 128, size=40)
    want = ref.forward(model, params, jnp.asarray([seq]))[0]
    got, _ = _paged_logits(cfg, params, seq, 19, round_cache=jnp.bfloat16)
    scale = float(jnp.abs(want).max())
    gap = float(jnp.abs(got - want[18:39]).max())
    assert gap > LOGIT_TOL * scale, (gap, scale)


def _ref_greedy(model, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = ref.forward(model, params, jnp.asarray([seq]))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


@pytest.fixture(scope="module")
def served(small):
    """Four prompts through the engine (3 slots, so one waits), 30
    new tokens each; the reference's greedy continuation of two."""
    model, cfg, params = small
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, size=n).tolist() for n in (5, 19, 30, 12)]
    want = [_ref_greedy(model, params, p, 30) for p in prompts[:2]]
    return prompts, want


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_engine_serves_the_references_greedy_tokens(small, served, layout):
    _, cfg, params = small
    prompts, want = served
    eng = ContinuousBatcher(
        cfg, params, n_slots=3, max_len=64, max_new_tokens=30, chunk=4,
        kv_layout=layout, page_size=PAGE, pad_id=-1,
    )
    outs = eng.generate_all(prompts)
    assert [o.tolist() for o in outs[:2]] == want
    if layout == "paged":
        eng.allocator.check()
        eng.allocator_win.check()
        assert eng.allocator.used_pages == 0 and eng.rings.pages_held == 0
        # contexts of up to 60 through rings of 4 pages: wrapped
        assert eng.rings.pages_freed_behind > 2 * eng.rings.ring_pages


def test_dense_generate_agrees_with_reference(small, served):
    _, cfg, params = small
    prompts, want = served
    out = decode.generate(cfg, params, jnp.asarray([prompts[1]]), 30)
    assert out[0, len(prompts[1]):].tolist() == want[1]


def test_preempted_wrapped_slot_replays_to_the_same_tokens(small, served):
    """Pool pressure preempts the coldest slot after its ring has
    wrapped; resume-by-replay prefills prompt + emitted tokens into a
    new ring and the greedy stream goes on byte for byte."""
    _, cfg, params = small
    prompts, _ = served
    kw = dict(n_slots=3, max_len=64, max_new_tokens=30, chunk=4,
              kv_layout="paged", page_size=PAGE, pad_id=-1)
    calm = ContinuousBatcher(cfg, params, **kw).generate_all(prompts)
    # 17 pages of the full class: three requests of 9-15 pages cannot
    # all stay, so admissions preempt
    tight = ContinuousBatcher(cfg, params, n_pages=30, **kw)
    outs = tight.generate_all(prompts)
    assert tight.paged_stats()["swap_preemptions"] > 0
    assert [o.tolist() for o in outs] == [o.tolist() for o in calm]
    tight.allocator.check()
    tight.allocator_win.check()
    assert tight.rings.pages_held == 0


def _stepped(cfg, params, prompts, depth, **kw):
    """Drives `step()` by hand with admissions landing mid-run: two
    requests at the start, one after three steps, one after five.
    Which call a token surfaces in depends on `depth`; which tokens a
    request gets does not. Returns every request's stream and key."""
    eng = ContinuousBatcher(
        cfg, params, n_slots=3, max_len=64, max_new_tokens=30, chunk=4,
        kv_layout="paged", page_size=PAGE, pad_id=-1, async_depth=depth,
        **kw,
    )
    late = {3: prompts[2], 5: prompts[3]}
    ids = [eng.submit(p) for p in prompts[:2]]
    streams, n = {}, 0
    while eng.has_work() or n <= max(late):
        if n in late:
            ids.append(eng.submit(late[n]))
        for idx, toks, _fin in eng.step():
            streams.setdefault(idx, []).extend(toks)
        n += 1
        assert n < 200
    keys = [eng._requests[i].prng_key.tolist() for i in ids]
    eng.allocator.check()
    eng.allocator_win.check()
    assert eng.allocator.used_pages == 0 and eng.rings.pages_held == 0
    return [streams[i] for i in ids], keys, eng


@pytest.mark.parametrize(
    "sampling",
    [{}, dict(temperature=0.8, top_k=20, seed=11)],
    ids=["greedy", "sampled"],
)
def test_a_dispatch_in_flight_serves_the_same_streams(small, served, sampling):
    """Window and full layers, dropless experts, two classes of pages:
    the engine's default order (a dispatch left in flight behind every
    step) against the order that harvests in the same call."""
    _, cfg, params = small
    prompts, want = served
    sync, sync_keys, _ = _stepped(cfg, params, prompts, 0, **sampling)
    flight, keys, eng = _stepped(cfg, params, prompts, 1, **sampling)
    assert flight == sync
    assert keys == sync_keys
    assert all(len(s) == 30 for s in flight)
    if not sampling:
        assert flight[:2] == want
    # the rings went round under the dispatch in flight too
    assert eng.rings.pages_freed_behind > 2 * eng.rings.ring_pages
    # and the default is the order with a dispatch in flight
    assert ContinuousBatcher(
        cfg, params, n_slots=1, max_len=16, kv_layout="paged",
        page_size=PAGE,
    ).async_depth == 1


def test_step_span_counts_pages_and_expert_load(small, served):
    from dlrover_tpu.common import trace

    _, cfg, params = small
    prompts, _ = served
    eng = ContinuousBatcher(
        cfg, params, n_slots=3, max_len=64, max_new_tokens=30, chunk=4,
        kv_layout="paged", page_size=PAGE, pad_id=-1,
    )
    since = time.time()
    eng.generate_all(prompts)
    recs = trace.snapshot(since, time.time())
    steps = [r[trace.COUNTS] for r in recs if r[trace.NAME] == "engine.step"]
    admits = [r[trace.COUNTS] for r in recs if r[trace.NAME] == "engine.admit"]
    assert any(s.get("window_pages_freed", 0) > 0 for s in steps)
    loaded = [s for s in steps if "moe_pairs" in s]
    assert loaded and all(
        s["moe_pairs"] == s["moe_steps"] * 3 * cfg.moe_top_k * cfg.n_layers
        and s["moe_max_load"] >= s["moe_mean_load"] > 0
        and s["pages_window"] <= 3 * eng.rings.ring_pages
        for s in loaded
    )
    assert sorted(a["window_cells"] for a in admits) == sorted(
        min(len(p), WINDOW) for p in prompts
    )
    # the rows a layer's grouped kernels are handed for the bucket:
    # every pair, and less than a sub-tile more for each expert
    from dlrover_tpu.models import moe

    for a in admits:
        pairs = a["bucket"] * cfg.moe_top_k
        assert a["moe_rows"] == moe.dropless_rows(pairs, cfg.n_experts)
        assert pairs <= a["moe_rows"] <= pairs + 15 * cfg.n_experts


# ---- the windowed kernel ---------------------------------------------------


def _ring_case(lengths, ring_pages=6, window=32, ps=8, kv=2, hd=32, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    b = len(lengths)
    n_pages = b * ring_pages + 1
    pool = {
        "k": jax.random.normal(ks[0], (2, n_pages, ps, kv, hd)),
        "v": jax.random.normal(ks[1], (2, n_pages, ps, kv, hd)),
    }
    table = jnp.asarray(
        np.random.RandomState(seed).permutation(np.arange(1, n_pages))
        .reshape(b, ring_pages), jnp.int32)
    q = jax.random.normal(ks[2], (b, 4, hd))
    return q, pool, table, jnp.asarray(lengths, jnp.int32), window


@pytest.mark.parametrize("lengths", [[5, 40, 131], [32, 33, 48], [1, 300, 47]])
def test_window_kernel_against_gathered_view(lengths):
    q, pool, table, lens, window = _ring_case(lengths)
    want = pa.paged_attention(
        q, pool, table, lens, impl="reference", layer=1, window=window)
    got = pa.paged_attention(
        q, pool, table, lens, impl="kernel", layer=1, window=window)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # and the gathered view against the cells themselves
    ps, ring = pool["k"].shape[2], table.shape[1]
    for b, n in enumerate(lengths):
        cells = range(max(0, n - window), n)
        pages = [int(table[b, (c // ps) % ring]) for c in cells]
        k = jnp.stack([pool["k"][1, p, c % ps] for p, c in zip(pages, cells)])
        v = jnp.stack([pool["v"][1, p, c % ps] for p, c in zip(pages, cells)])
        s = jnp.einsum("krd,nkd->krn", q[b].reshape(2, 2, -1), k) / math.sqrt(32)
        o = jnp.einsum("krn,nkd->krd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(want[b], o.reshape(4, -1), atol=2e-6)


@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_no_window_is_todays_program_byte_for_byte(impl):
    """`window=None` must leave the paged attention as it was: the
    same jaxpr as a call that does not know the argument, and the
    same bytes out."""
    q, pool, table, lens, _ = _ring_case([5, 40, 47])
    old = lambda q, pool, table, lens: pa.paged_attention(
        q, pool, table, lens, impl=impl, layer=1)
    new = lambda q, pool, table, lens: pa.paged_attention(
        q, pool, table, lens, impl=impl, layer=1, window=None)
    assert str(jax.make_jaxpr(old)(q, pool, table, lens)) == str(
        jax.make_jaxpr(new)(q, pool, table, lens))
    np.testing.assert_array_equal(
        old(q, pool, table, lens), new(q, pool, table, lens))


def test_flash_forward_band_against_reference():
    from dlrover_tpu.ops.attention import reference_attention

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 512, 4, 32))
    k = jax.random.normal(ks[1], (1, 512, 2, 32))
    v = jax.random.normal(ks[2], (1, 512, 2, 32))
    want = reference_attention(q, k, v, window=100)
    got = fa.flash_attention(q, k, v, block_q=128, block_k=128, window=100)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(want - reference_attention(q, k, v)).max()) > 1e-2


# ---- YaRN ------------------------------------------------------------------


def test_yarn_frequencies_at_the_published_numbers():
    """theta 500000, factor 16, original length 8192, beta 32 and 1,
    head_dim 128: the formula of ISSUE 31, written out here."""
    theta, factor, original, d = 500000.0, 16.0, 8192, 128
    spec = llama.RopeSpec(
        theta=theta, yarn_factor=factor, original_len=original,
        beta_fast=32, beta_slow=1,
    )
    freqs, attention_factor = llama.yarn_frequencies(spec, d)
    dim = lambda r: d * math.log(original / (2 * math.pi * r)) / (
        2 * math.log(theta))
    lo, hi = math.floor(dim(32)), math.ceil(dim(1))
    assert (lo, hi) == (18, 35)
    for i in range(d // 2):
        f = theta ** (-2 * i / d)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want = f * (1 - ramp) + f / factor * ramp
        assert freqs[i] == pytest.approx(want, rel=1e-6)
    assert attention_factor == pytest.approx(1.2772588722239782, rel=1e-12)
    assert attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    # the reference's own frequencies agree
    model = tiny.published_model()
    rf, ra = ref.rope_frequencies(
        model["rope_parameters"]["full_attention"], d)
    np.testing.assert_allclose(rf, freqs, rtol=1e-6)
    assert ra == pytest.approx(attention_factor)
    plain, one = llama.yarn_frequencies(llama.RopeSpec(theta=theta), d)
    assert one == 1.0 and plain[1] == pytest.approx(theta ** (-2 / d))


# ---- what is refused -------------------------------------------------------


@pytest.mark.parametrize("knobs", [
    dict(prefix_cache_rows=4),
    dict(kv_tier_bytes=1 << 20),
    dict(replica_role="prefill"),
    dict(replica_role="decode"),
    dict(spec_draft_len=2),
    dict(adapter_registry=object()),
    dict(weight_quant="int8"),
    dict(mesh_spec=2),
    dict(kv_quant=True),
    dict(prefill_chunk=16),
])
def test_unserved_combinations_are_refused_by_name(small, knobs):
    _, cfg, params = small
    with pytest.raises(ValueError, match="window and full attention"):
        ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, kv_layout="paged",
            page_size=PAGE, **knobs,
        )


def test_training_forward_refuses_the_configuration(small):
    _, cfg, params = small
    with pytest.raises(ValueError, match="llama.apply"):
        llama.apply(cfg, params, jnp.zeros((1, 8), jnp.int32))
    # experts routed without dropping alone are refused too: the
    # training layer would fall back to capacity routing
    dense = dataclasses.replace(
        cfg, layer_pattern=(), sliding_window=0, rope_full=None,
        rope_window=None,
    )
    with pytest.raises(ValueError, match="dropless"):
        llama.apply(dense, params, jnp.zeros((1, 8), jnp.int32))


def test_long_prompts_are_bucketed_by_512(small):
    """One rule for every model: above 1024 a cold prompt's bucket
    steps by 512, unless the prefix cache (or the tier, or a handoff
    role), which reckon in powers of two, is on."""
    _, cfg, params = small
    eng = ContinuousBatcher(
        cfg, params, n_slots=2, max_len=3584, kv_layout="paged",
        page_size=16, n_pages=300,
    )
    assert [eng._prompt_bucket(p) for p in (5, 600, 1024, 1025, 2100,
                                            3072, 3073, 3583)] == [
        16, 1024, 1024, 1536, 2560, 3072, 3584, 3584]
    plain = dataclasses.replace(llama.LlamaConfig.tiny(), max_seq_len=4096)
    weights = llama.init_params(plain, jax.random.PRNGKey(0))
    for kw, want in (({}, 2560), ({"prefix_cache_rows": 2}, 3584),
                     ({"replica_role": "prefill"}, 3584)):
        other = ContinuousBatcher(
            plain, weights, n_slots=2, max_len=3584, kv_layout="dense", **kw)
        assert other._prompt_bucket(2100) == want, kw
        assert other._prompt_bucket(600) == 1024
