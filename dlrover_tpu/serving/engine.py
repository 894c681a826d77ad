"""Continuous-batching generation engine: slot-based KV cache, per-slot
lengths, admit-on-release.

Extracted from rl/serve.py so serving is not an RL concern: the engine
is a generic front-end over models/decode.py that both the PPO rollout
path (rl/ppo.py imports it back through the rl/serve.py shim) and the
inference gateway (serving/scheduler.py) drive. Behavior is unchanged —
the parity tests in tests/test_serve.py pin it.

Reference parity: atorch/rl/inference_backend/vllm_backend.py:24 — the
reference hands PPO rollouts to vLLM for continuous batching + paged
KV. TPU re-design, not a port:

- ONE static-shape compiled program does all the stepping: a fixed
  bank of `n_slots` cache rows, each at its OWN position (the vector-
  `pos` path of models/decode.py). No dynamic shapes, no recompiles —
  mixed-length traffic changes only the DATA (which slots are live),
  never the program.
- "paged KV" collapses to slot reuse: a released row is re-admitted by
  overwriting its cache prefix (prefill_into_slot); cells beyond the
  new prompt are dead by the position mask, so no page table is
  needed at this granularity.
- prompt-prefix reuse (vLLM's prefix caching) is admission-time and
  copy-based: `prefix_cache_rows > 0` keeps a radix tree of
  block-aligned prompt prefixes (serving/prefix_cache.py) whose K/V
  live in a second exact-dtype bank; a matched admission installs the
  prefix with one compiled copy and prefills ONLY the suffix bucket.
  A fleet sharing a 512-token system prompt pays its prefill once,
  not per request — and the chunk-scan program never changes.
- host↔device chatter is amortized by decoding `chunk` steps per
  dispatch inside one lax.scan (a finished slot idles at most
  chunk-1 steps before the host swaps in the next request).
- sampling (temperature/top-k/top-p, EOS discipline) reuses
  decode.py's own mask helpers, so serve and generate() cannot drift.

The win over lockstep generate(): a fixed batch runs every row to the
LONGEST request's length (finished rows burn steps emitting pad);
here a finished slot is refilled within one chunk, so the chip's
step-rate turns into useful tokens at any length mix.

Two driving modes share one loop body:

- `generate_all(prompts)` — batch drain (the PPO rollout path):
  submit everything, run to completion, return continuations in
  submission order.
- `step()` — incremental (the serving path): admit from the queue
  into free slots, run ONE chunk, and return per-request token
  deltas as they are emitted. The scheduler streams these to
  clients and `retire()`s finished requests.

Device residency + async dispatch (the perf layer over both modes):

- Slot state (`tok`/`pos`/`done`/`limit`/`slot_key`) lives on device
  between dispatches; admissions and cancels apply as tiny jit'd
  scatter updates instead of re-uploading five host arrays per
  chunk. Host numpy mirrors of the same state (same attribute
  names) keep `_admit`/scheduler decisions host-cheap; they are
  refreshed ONLY from a dispatch's fetched outputs, never by a
  fresh blocking copy — `_to_host` is the module's single
  device→host materialization point (tests/test_layering.py lints
  this).
- A step keeps ONE dispatch in flight (`async_depth=1`, the default
  since PR 36): dispatch N is enqueued via JAX async dispatch with
  `copy_to_host_async()` started on its outputs, and `step()` returns
  the events of dispatch N-1 — so the caller's event delivery,
  streaming, journaling, metrics and its next admission pass run
  under dispatch N's device compute instead of serializing with it.
  On the chip a served step's idle share was 11-12% with the host and
  the device taking turns (PERF.md §6, PR 36), and a closed loop
  loses throughput one for one with it: so this is what an engine
  built with no word on it does. `async_depth=0` harvests in the same
  call and is the parity oracle the tests state by name. Either way
  the dispatch SEQUENCE is identical — drafting and admission always
  see the fully-harvested state of dispatch N-1 before dispatch N is
  built — so token streams are byte-identical across depths
  (DEVIATIONS §9 records the staleness contract this leaves the
  scheduler).
- An admission never fetches from the device: `_admit` returns once
  the prompt's forward and the state scatters are ENQUEUED. The
  request's key is split off the engine's on the host
  (serving/host_prng.py: the same threefry bits), because a split on
  the device is fetched behind the admission's own prefill, and the
  second admission of a step, the rings and the chunk dispatch would
  then be prepared with the device idle. tests/test_layering.py
  lints `_admit` like any other step-path function, and
  tests/test_serving_admit_no_fetch.py spies on every branch.
"""

import contextlib
import dataclasses
import time
from collections import deque
from functools import partial, wraps
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import trace
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.models.decode import (
    _block_of,
    _check_adapters,
    _check_positional_capacity,
    _dropless,
    _forward_paged,
    _warp,
    moe_counts_shape,
    decode_step,
    gather_pool_view,
    init_hybrid_pools,
    init_kv_cache,
    init_page_pool,
    install_exact_row,
    paged_decode_step,
    paged_install_hybrid,
    paged_install_row,
    paged_prefill_chunk,
    paged_verify_step,
    pool_copy_page,
    pool_put_row,
    pool_take_row,
    prefill_chunk_into_slot,
    prefill_exact_row,
    prefill_into_slot,
    prefill_suffix_row,
    scatter_pool_window,
    spec_accept_greedy,
    spec_accept_sampled,
    verify_step,
)
from dlrover_tpu.models.moe import dropless_rows
from dlrover_tpu.ops.quantization import (
    QuantizedWeight,
    quantize_int8,
    stochastic_round_int8,
    use_quant_matmul_kernel,
    weight_quant_block,
)
from dlrover_tpu.parallel.mesh import (
    named,
    serving_adapter_specs,
    serving_kv_spec,
    serving_mesh,
    serving_mesh_spec,
    serving_mesh_tp,
    serving_weight_quant_specs,
)
from dlrover_tpu.parallel.sharding import replicated, shard_tree
from dlrover_tpu.serving.adapters import DeviceAdapterCache
from dlrover_tpu.serving import host_prng
from dlrover_tpu.serving import kv_tier as _kv_tier
from dlrover_tpu.serving.paged_kv import (
    TRASH_PAGE,
    OutOfPages,
    PageAllocator,
    WindowRings,
)
from dlrover_tpu.serving.prefix_cache import RadixPrefixCache
from dlrover_tpu.serving.speculative import SpeculativeDecoder


# GSPMD param layout for a serving replica (ISSUE/ DEVIATIONS §11):
# ONLY the QKV projections shard, on their head/output columns —
# splitting a matmul's output dim leaves every output element's
# contraction intact, which is what keeps tp>1 byte-identical to tp=1
# (see the parity note atop models/decode.py). Out projection, MLP,
# embedding, head and norms stay replicated: they run after the
# attention output is all-gathered back to full width, so sharding
# them would split a contraction and reassociate float adds. GPT's
# fused-qkv weight matches no rule and stays replicated; its q/k/v
# still shard through the activation constraints.
_SERVING_PARAM_RULES = (
    (r"layers/wq$", ("tp",)),
    (r"layers/wk$", ("tp",)),
    (r"layers/wv$", ("tp",)),
)

# The large matmul weights weight_quant="int8" re-stores as per-block
# int8 (ops/quantization.QuantizedWeight). Name-based on the stacked
# layer dict, covering both families: llama (wq/wk/wv/wo + SwiGLU
# gate/up/down) and GPT-2 (fused wqkv/wo + GELU up/down). Everything
# else — norms, biases, embeddings, MoE expert stacks — stays dense:
# gathers need the dense table, and small vectors have no bytes worth
# saving. The untied llama lm_head quantizes separately below.
_WQ_LAYER_WEIGHTS = frozenset(
    ("wq", "wk", "wv", "wo", "wqkv", "w_gate", "w_up", "w_down")
)


def _serving_param_shardings():
    from jax.sharding import PartitionSpec

    # quant specs FIRST is not required — the dense rules are
    # $-anchored, so a QuantizedWeight's q8/s8 sub-paths
    # (layers/wq/q8) can only match the quant rules; dense trees
    # never produce those paths. Quantized wo/MLP/head leaves match
    # nothing and replicate, exactly like their dense forms.
    return [
        (pat, PartitionSpec(None, None, *axes))
        for pat, axes in _SERVING_PARAM_RULES
    ] + list(serving_weight_quant_specs())


def _parse_mesh_tp(mesh_spec) -> int:
    """The `mesh_spec` knob accepts an int tp degree, a {"tp": n}
    dict, or a parallel.mesh.MeshSpec (its tensor axis)."""
    if isinstance(mesh_spec, bool):
        raise ValueError(f"mesh_spec must be an int tp degree, a "
                         f"{{'tp': n}} dict or a MeshSpec, got "
                         f"{mesh_spec!r}")
    if isinstance(mesh_spec, int):
        return mesh_spec
    if isinstance(mesh_spec, dict):
        extra = set(mesh_spec) - {"tp"}
        if extra:
            raise ValueError(
                f"mesh_spec dict supports only the 'tp' axis for "
                f"serving, got extra axes {sorted(extra)}"
            )
        return int(mesh_spec.get("tp", 1))
    tensor = getattr(mesh_spec, "tensor", None)
    if tensor is not None:
        return int(tensor)
    raise ValueError(
        f"mesh_spec must be an int tp degree, a {{'tp': n}} dict or "
        f"a MeshSpec, got {mesh_spec!r}"
    )


def _refuse_unserved(cfg, **asked) -> None:
    """A model that mixes window and full attention layers, keeps a
    latent cache, or routes its experts without dropping, is served
    by the plain paged (or dense) path only. Every other combination
    is refused here, at construction and by name: none may mis-serve
    silently."""
    what = [
        name for name, on in (
            ("window and full attention layers mixed",
             getattr(cfg, "hybrid", False)),
            ("a latent cache", getattr(cfg, "latent", False)),
            ("experts routed without dropping",
             getattr(cfg, "n_experts", 0) > 0
             and getattr(cfg, "moe_routing", "") == "dropless"),
            ("generation by diffusion over blocks", _block_of(cfg) > 0),
        ) if on
    ]
    knobs = sorted(name for name, on in asked.items() if on)
    if what and knobs:
        raise ValueError(
            f"a model with {' and '.join(what)} is not served with "
            f"{', '.join(knobs)}: the prefix cache, the host KV tier, "
            "the handoff between replicas, speculative decoding, "
            "adapters, int8 weights or KV, chunked prefill and tp > 1 "
            "move runs of k and v pages of one class, know dense "
            "feed-forward layers only and count a token a forward"
        )


def _pad_bucket(n: int, lo: int = 16) -> int:
    """Next power-of-two bucket (≥ lo) — bounds prefill recompiles to
    log2(max_len) distinct shapes."""
    b = lo
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class _Request:
    idx: int                 # submission order
    prompt: np.ndarray       # [P] true tokens
    max_new: int = 0         # per-request cap (0 = engine default)
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # explicit sampling key (crash resume continues a journaled key
    # stream); None = the engine draws one from its seed at admission
    prng_key: Optional[np.ndarray] = None
    # set by preempt-and-swap: the request was swapped to host and
    # re-queued for resume-by-replay (paged layout, pool pressure)
    preempted: bool = False
    # weight versions whose dispatches emitted this request's tokens
    # (elastic refresh observability: exactly one entry under the
    # deferred fence; a second only across an opted-in live swap)
    versions: set = dataclasses.field(default_factory=set)
    # a serving/handoff.py KVHandoff package: the prompt's KV was
    # prefilled on another replica and rides in `adopted.data` —
    # admission installs it instead of running a prefill (cleared at
    # admission, so a later preemption falls back to plain replay)
    adopted: Optional[Any] = None
    # how many of `out` are already folded into `prompt` by earlier
    # preemptions — a second preemption must not re-append them
    folded: int = 0
    # multi-adapter serving: the registry id this request decodes
    # under (None = base model) and its resolved device-bank slot.
    # The slot is PINNED from submit to retire/cancel, so it cannot
    # be remapped under a live (or preempted) request.
    adapter_id: Optional[str] = None
    adapter_slot: int = 0


# one step() event: (request idx, tokens emitted this chunk, finished)
StepEvent = Tuple[int, List[int], bool]


# ---------------------------------------------------------------------------
# Compiled-program caches. The jitted closures are built per
# (config, knobs) key, NOT per engine instance: a second engine with
# the same shapes — a restarted replica, the bench's cold/warm passes,
# a test suite full of tiny engines — reuses the first one's programs
# (and their XLA compile caches) instead of re-tracing everything.
# Split in two because the admission/pool programs don't depend on the
# sampling knobs: a greedy engine and a sampled engine over the same
# model share every admit compile.

_CHUNK_PROGRAMS: Dict[Any, Any] = {}
_ADMIT_PROGRAMS: Dict[Any, Any] = {}
_SPEC_PROGRAMS: Dict[Any, Any] = {}


def _cached_program(cache: Dict[Any, Any], key, build):
    try:
        prog = cache.get(key)
    except TypeError:  # unhashable config: fall back to per-instance
        return build()
    if prog is None:
        prog = cache[key] = build()
    return prog


def _kernel_cache_tag() -> tuple:
    """Extra program-cache key component for forced-kernel runs.

    DLROVER_TPU_FORCE_KERNELS lives in the environment, not in cfg or
    mesh, yet it changes which attention body the traced program
    contains (shard_mapped Pallas kernel vs XLA reference). Without
    this tag a forced engine and an unforced engine with identical
    (cfg, mesh, ...) would share one cached program and silently run
    the wrong body. Unforced runs get the empty tuple so their keys
    stay byte-identical to what they were before the knob existed.
    """
    from dlrover_tpu.ops import flash_attention as fa

    return ("forced-kernels",) if fa.force_kernels() else ()


def _paged_step_takes_kernel(cfg, n_slots, pool, table, mesh) -> bool:
    """Whether the per-token decode step over THIS pool streams pages
    through the Pallas kernel — the one question that picks a paged
    program's execution strategy (per-step page-native decode vs
    gather-once / dense scan / scatter-back) and that
    `kernel_path` reports. A trace-time decision from shapes alone
    (`pool` leaves carry the leading layer axis; arrays, tracers and
    ShapeDtypeStructs all do), asked of the same gate
    models/decode.py dispatches on, so the program and its label
    cannot disagree."""
    if getattr(cfg, "attn_impl", "auto") == "reference":
        return False
    from dlrover_tpu.ops import paged_attention as pa

    if "ckv" in pool:
        # a latent pool: the latent variant's own gate
        return pa.use_kernel_latent(
            jax.ShapeDtypeStruct(
                (n_slots, cfg.n_heads, cfg.latent_width), cfg.dtype
            ),
            {"ckv": jax.ShapeDtypeStruct(
                pool["ckv"].shape[1:], pool["ckv"].dtype)},
            jax.ShapeDtypeStruct(tuple(table.shape), jnp.int32),
            cfg.kv_lora_rank,
        )
    # a diffusion block's queries, and the carried block's before
    # them, ride the walk as further heads
    probe_q = jax.ShapeDtypeStruct(
        (n_slots, cfg.n_heads * max(2 * _block_of(cfg), 1), cfg.head_dim),
        cfg.dtype,
    )
    if "full" in pool:
        # two classes of pages (a window and a full one) of one page
        # shape: the full class answers for both
        pool = pool["full"]
    probe_pool = {
        name: jax.ShapeDtypeStruct(arr.shape[1:], arr.dtype)
        for name, arr in pool.items()
    }
    probe_table = jax.ShapeDtypeStruct(tuple(table.shape), jnp.int32)
    return pa.use_kernel(
        probe_q, probe_pool, probe_table, tp=serving_mesh_tp(mesh)
    )


def _lora_operand(abank, aidx, row=None):
    """Assemble the `adapters` operand models/decode.py expects from
    the stacked device bank + a per-row adapter-index vector (`row`
    picks one slot's entry: a one-row prefill). None when the program
    was handed no bank: adapters are an optional operand of every
    program, never a second program."""
    if abank is None:
        return None
    if row is not None:
        aidx = aidx[row][None]
    return {
        "bank": {k: v for k, v in abank.items() if k != "scale"},
        "idx": aidx,
        "scale": abank["scale"],
    }


# `keys` is PER-SLOT ([B, 2] uint32), not one engine-global key: a
# slot's noise stream depends only on its own key, never on batch
# composition. That is what makes crash resume exact — the scheduler
# journals each slot's key after every dispatch, and a request
# re-admitted elsewhere with that key draws the same sample an
# uncrashed run would have. A live slot burns exactly one split per
# scan step (== one per emitted token while live). Every layout and
# every program shares this one post-logits advance, so they sample,
# stop and cap identically — the byte-parity contract of
# kv_layout="paged" reduces to the forward producing identical
# logits, which the gathered-view attention guarantees.
def _advance(sampling, logits, tok, pos, done, limit, keys):
    pad_id, eos_id, temperature, top_k, top_p = sampling
    with jax.named_scope("sample"):
        if temperature <= 0.0:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            pair = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
            keys, subs = pair[:, 0], pair[:, 1]
            nxt = jax.vmap(
                lambda l, kk: jax.random.categorical(kk, l)
            )(_warp(logits, temperature, top_k, top_p), subs).astype(
                jnp.int32
            )
    nxt = jnp.where(done, pad_id, nxt)
    hit_eos = (
        (nxt == eos_id) if eos_id is not None else jnp.zeros_like(done)
    )
    # tokens generated through this step = pos+2-prompt_len (carry
    # enters at prompt_len-1), so the length cap
    # limit = prompt_len + max_new fires at pos+2 >= limit
    new_done = done | hit_eos | (pos + 2 >= limit)
    pos = jnp.where(done, pos, pos + 1)
    tok = jnp.where(done, tok, nxt)
    return tok, pos, new_done, keys, nxt


def _decode_scan(
    cfg, mesh, sampling, cache, params, tok, pos, done, limit, keys, k,
    table=None, table_win=None, adapters=None,
):
    """THE decode loop: k steps over every slot, whatever holds the
    KV. Returns (cache, tok, pos, done, keys, emitted [B, k]), and the
    routed pairs per expert held here after them, where the pool is
    stepped page by page and the experts are routed without dropping.

    `cache` is a dense bank (`table` None), a stacked page pool (of k
    and v, or of latent rows), or the pair of pools of a model with
    window layers (`table_win`: the slots' rings, as the host left
    them before this dispatch). The
    page table rides read-only: it changes only via host-side
    admission/CoW scatters, never inside a chunk. Done rows route
    through the trash page (page 0) HERE, so releasing a finished
    slot's pages is pure host accounting — no table-parking dispatch
    on the finish/retire/preempt path; rows finishing MID-chunk still
    own their pages (the host frees them only after harvesting this
    dispatch), so their remaining frozen rewrites stay in-bounds
    either way. `adapters` (see `_lora_operand`) rides read-only too:
    base rows carry index 0 — the permanent zero adapter — so a mixed
    batch is ONE dispatch whatever its adapter composition.

    A pool is stepped one of two ways, chosen at trace time from
    shapes (`_paged_step_takes_kernel`; two classes of pages and a
    latent pool always the first):
      kernel — per-step paged_decode_step, whose S==1 path streams
      physical pages through the Pallas paged-attention kernel
      without materializing a dense view;
      reference — gather the dense view ONCE, scan the dense step
      over it (byte parity by construction: it IS the dense program
      over the same bytes), and scatter the k-wide written window
      back to pages afterwards. A per-step gather would copy the full
      cache once per token — the difference between ~parity and >2x
      dense TPOT on the CPU smoke."""
    pool, start = None, pos
    page_native = False
    if table is not None:
        table = jnp.where(done[:, None], 0, table)
        if table_win is not None:
            table_win = jnp.where(done[:, None], 0, table_win)
        page_native = (
            table_win is not None or "ckv" in cache
            or _paged_step_takes_kernel(
                cfg, tok.shape[0], cache, table, mesh)
        )
        if not page_native:
            pool, cache = cache, gather_pool_view(cache, table)

    def body(carry, _):
        cache, tok, pos, done, keys, pairs = carry
        if page_native:
            logits, cache, *counts = paged_decode_step(
                cfg, params, tok, cache, table, pos, mesh=mesh,
                adapters=adapters, table_win=table_win,
            )
        else:
            logits, cache, *counts = decode_step(
                cfg, params, tok, cache, pos, mesh=mesh,
                adapters=adapters,
            )
        tok, pos, done, keys, nxt = _advance(
            sampling, logits, tok, pos, done, limit, keys
        )
        if counts and pairs is not None:
            # dropless experts: routed pairs per expert, summed over
            # the layers and the k steps
            pairs = pairs + counts[0]
        return (cache, tok, pos, done, keys, pairs), nxt

    pairs = None
    if table_win is not None or (page_native and _dropless(cfg)):
        pairs = jnp.zeros(
            moe_counts_shape(cfg) if getattr(cfg, "n_experts", 0)
            else (1,), jnp.int32,
        )
    (cache, tok, pos, done, keys, pairs), emitted = jax.lax.scan(
        body, (cache, tok, pos, done, keys, pairs), None, length=k,
    )
    if pool is not None:
        cache = scatter_pool_window(pool, cache, table, start, k)
    out = (cache, tok, pos, done, keys, emitted.T)
    return out if pairs is None else out + (pairs,)


def _build_chunk_program(
    cfg, pad_id, eos_id, temperature, top_k, top_p, mesh=None
):
    """The chunk program: `_decode_scan` behind one jit a layout. The
    cache (bank or page pool) is the donated argument; the adapter
    bank and the per-slot adapter-index vector are optional trailing
    operands, so an adapterless engine's programs hold nothing of them."""
    scan = partial(
        _decode_scan, cfg, mesh, (pad_id, eos_id, temperature, top_k, top_p)
    )

    @partial(jax.jit, donate_argnums=(0,), static_argnums=(7,))
    def _run_chunk(
        cache, params, tok, pos, done, limit, keys, k,
        abank=None, aidx=None,
    ):
        return scan(
            cache, params, tok, pos, done, limit, keys, k,
            adapters=_lora_operand(abank, aidx),
        )

    @partial(jax.jit, donate_argnums=(0,), static_argnums=(8,))
    def _run_chunk_paged(
        pool, table, params, tok, pos, done, limit, keys, k,
        table_win=None, abank=None, aidx=None,
    ):
        return scan(
            pool, params, tok, pos, done, limit, keys, k, table=table,
            table_win=table_win, adapters=_lora_operand(abank, aidx),
        )

    return {"dense": _run_chunk, "paged": _run_chunk_paged}


def _diffusion_scan(
    cfg, steps, pool, params, blk, msk, prev, pos, done, limit, k, table
):
    """The decode loop of a model that generates by diffusion over
    blocks: k FORWARDS over every slot, each slot at its own phase of
    its own block, no host round trip inside. A slot's state is its
    block: `blk` [B, block] the ids (the mask id where still masked),
    `msk` [B, block] which positions are masked, `pos` [B] the block's
    first position (a multiple of the block length), and `prev`
    [B, block] the ids of the block before it while that block's keys
    and values are still owed to the pool (-1: nothing is owed).

    A block takes `steps` forwards and no more. One forward runs TWO
    blocks' positions a slot over the paged pool (`_forward_paged`
    with `carried`): `prev`, carried for its keys and values only,
    which sees the pool up to its own end, and the block, which sees
    the pool, the carried rows' cells written in this very forward,
    and itself. The carried rows' inputs depend on nothing but the
    pool and themselves, so what they store is what the published
    loop's commit forward stores, and they stop before the head. A
    slot that owes nothing (a block's later forwards; a request's
    first block, whose earlier cells the prefill installed; a done
    row) carries dead rows, which write to the trash page. Then, a
    slot, the forward DENOISES: every masked position takes its
    arg-max id and its confidence (that id's softmax probability),
    and the ceil(block / steps) most confident masked positions are
    unmasked, ties to the lower position (`low_confidence_static`).
    Where that leaves no position masked the block's ids are FINAL:
    they are emitted at once, and the slot moves one block on, all
    masked, owing the finished block to its next forward; or it is
    done, where the next block starts at or past its limit (nothing
    reads a request's last block's cells; its position then stays,
    so a done row's frozen rewrites land in cells it owns).

    Returns (pool, blk, msk, prev, pos, done, took [B, k, block], ids
    [B, k, block], phase [B, k], fused [B, k], routed pairs per
    expert). A forward: `took` the ids it unmasked, -1 elsewhere;
    `ids` the block after it; `phase` 0 for a done row, 1 for a
    forward that left the block unfinished, 2 for one that finished
    it; `fused` whether it carried a finished block."""
    block = cfg.block_length
    count = -(-block // steps)
    table = jnp.where(done[:, None], 0, table)
    offs = jnp.arange(block, dtype=jnp.int32)
    both = jnp.concatenate([offs - block, offs])
    before = offs[None, :] < offs[:, None]        # [i, j]: j lower than i
    mask_id = jnp.int32(cfg.mask_token_id)

    def body(carry, _):
        pool, blk, msk, prev, pos, done, pairs = carry
        fused = (prev[:, 0] >= 0) & ~done
        logits, pool, *counts = _forward_paged(
            cfg, params,
            jnp.concatenate([jnp.maximum(prev, 0), blk], axis=1),
            pool, table,
            # a dead row's positions before the first block: anywhere
            jnp.maximum(pos[:, None] + both[None, :], 0),
            carried=fused,
        )
        with jax.named_scope("diffusion_unmask"):
            top = jnp.max(logits, axis=-1)
            best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # the arg-max id's softmax probability
            conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
            score = jnp.where(msk, conf, -1.0)
            ahead = (score[:, None, :] > score[:, :, None]) | (
                (score[:, None, :] == score[:, :, None]) & before[None]
            )
            take = (
                msk & (jnp.sum(ahead, axis=-1) < count) & ~done[:, None]
            )
        with jax.named_scope("diffusion_commit"):
            ids = jnp.where(take, best, blk)
            left = msk & ~take
            final = ~jnp.any(left, axis=-1) & ~done
            last = final & (pos + block >= limit)
            on = (final & ~last)[:, None]
            took = jnp.where(take, best, -1)
            phase = jnp.where(done, 0, jnp.where(final, 2, 1))
            prev = jnp.where(on, ids, -1)
            blk = jnp.where(on, mask_id, ids)
            msk = on | left
            pos = jnp.where(on[:, 0], pos + block, pos)
            done = done | last
        if counts and pairs is not None:
            pairs = pairs + counts[0]
        return (pool, blk, msk, prev, pos, done, pairs), (
            took, ids, phase.astype(jnp.int8), fused
        )

    pairs = (
        jnp.zeros(moe_counts_shape(cfg), jnp.int32)
        if _dropless(cfg) else None
    )
    (pool, blk, msk, prev, pos, done, pairs), per_forward = jax.lax.scan(
        body, (pool, blk, msk, prev, pos, done, pairs), None, length=k,
    )
    out = (pool, blk, msk, prev, pos, done) + tuple(
        jnp.swapaxes(x, 0, 1) for x in per_forward
    )
    return out if pairs is None else out + (pairs,)


def _build_diffusion_program(cfg, steps):
    """The chunk program of a model that generates by diffusion over
    blocks: `_diffusion_scan` behind one jit, over the paged pool
    (donated). `steps` is the denoising steps a block, the sampling
    parameter this family has (one chip: `_refuse_unserved`)."""
    scan = partial(_diffusion_scan, cfg, steps)

    @partial(jax.jit, donate_argnums=(0,), static_argnums=(9,))
    def _run_chunk_blocks(
        pool, table, params, blk, msk, prev, pos, done, limit, k
    ):
        return scan(
            pool, params, blk, msk, prev, pos, done, limit, k, table
        )

    return {"paged": _run_chunk_blocks}


def _build_pf_chunk_program(
    cfg, pad_id, eos_id, temperature, top_k, top_p, mesh=None
):
    """Interleaved chunked-prefill variant of the chunk program: ONE
    compiled dispatch runs up to `prefill_chunk` tokens of a pending
    prompt's prefill (positions [pstart, pstart+C) of slot `pslot`)
    AND a k-step decode scan over every live slot — so a cold
    admission stops monopolizing the step loop and decode TPOT stays
    bounded while long prompts stream in chunk by chunk.

    The decode half IS the chunk program's `_decode_scan`; the
    prefilling slot rides through it FROZEN (device done=True — its
    rewrites are dead by the position mask dense-side and
    trash-routed paged-side), so interleaving changes nothing the
    live rows can observe. The prefill half writes through
    models/decode.py's chunked-prefill primitives, which attend the
    already-installed cells — the `prefill_suffix_row` byte-exactness
    argument, chunk by chunk — under the PREFILLING slot's adapter
    (its prompt K/V must come from the adapted projections; the
    decode half rides the full per-slot index vector as usual).

    `frontier` is the per-slot partial-write frontier ([B] int32,
    device-resident beside tok/pos/done); the program advances
    `pslot`'s entry past the chunk it just wrote. Built only when
    `prefill_chunk > 0`: the plain program, its cache keys, and the
    pc=0 engine are structurally untouched (the parity oracle)."""
    scan = partial(
        _decode_scan, cfg, mesh, (pad_id, eos_id, temperature, top_k, top_p)
    )

    @partial(jax.jit, donate_argnums=(0,), static_argnums=(8,))
    def _run_pf(
        cache, params, tok, pos, done, limit, keys, frontier, k,
        ptoks, pslot, pstart, abank=None, aidx=None,
    ):
        cache = prefill_chunk_into_slot(
            cfg, params, ptoks, cache, pslot, pstart, mesh=mesh,
            adapters=_lora_operand(abank, aidx, row=pslot),
        )
        frontier = frontier.at[pslot].set(pstart + ptoks.shape[0])
        cache, tok, pos, done, keys, emitted = scan(
            cache, params, tok, pos, done, limit, keys, k,
            adapters=_lora_operand(abank, aidx),
        )
        return cache, tok, pos, done, keys, frontier, emitted

    @partial(jax.jit, donate_argnums=(0,), static_argnums=(9,))
    def _run_pf_paged(
        pool, table, params, tok, pos, done, limit, keys, frontier,
        k, ptoks, pslot, pstart, abank=None, aidx=None,
    ):
        # the prefill writes through the slot's REAL table row —
        # gathered BEFORE the decode half trash-routes done rows
        # (the prefilling slot IS a done row to the decode scan)
        pool = paged_prefill_chunk(
            cfg, params, ptoks, pool, table[pslot], pstart, mesh=mesh,
            adapters=_lora_operand(abank, aidx, row=pslot),
        )
        frontier = frontier.at[pslot].set(pstart + ptoks.shape[0])
        pool, tok, pos, done, keys, emitted = scan(
            pool, params, tok, pos, done, limit, keys, k, table=table,
            adapters=_lora_operand(abank, aidx),
        )
        return pool, tok, pos, done, keys, frontier, emitted

    return {"dense": _run_pf, "paged": _run_pf_paged}


def _build_spec_program(
    cfg, pad_id, eos_id, temperature, top_k, top_p, mesh=None
):
    """The speculative alternative to the chunk scan: ONE verify
    forward over K+1 positions per slot, acceptance on device, and
    the same eos/limit/done discipline the chunk program applies —
    so a spec step and a chunk step are interchangeable mid-request
    (the adaptive controller switches between them freely).

    K is static (drafts' shape), so the whole thing is one trace: the
    host varies only the DATA (per-slot draft tokens and draft_len,
    zero for slots whose controller disabled speculation — those rows
    degenerate to a normal one-token step inside the same program).
    The adapted projections run inside the SAME verify forward, so a
    draft is judged against the adapter the slot decodes under.
    """

    def _accept(
        logits, tok, pos, done, limit, keys, drafts, draft_len
    ):
        b, k = drafts.shape
        if temperature <= 0.0:
            m, extra = spec_accept_greedy(logits, drafts, draft_len)
        else:
            # per-slot keys, like the chunk program: each row's
            # accept/resample noise comes from its own key stream
            pair = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
            keys, subs = pair[:, 0], pair[:, 1]
            probs = jax.nn.softmax(
                _warp(logits, temperature, top_k, top_p), axis=-1
            )

            def _row(kk, p, d, l):
                mm, ee = spec_accept_sampled(
                    kk, p[None], d[None], l[None]
                )
                return mm[0], ee[0]

            m, extra = jax.vmap(_row)(subs, probs, drafts, draft_len)
        # emitted layout: m accepted drafts, then the extra token
        # (correction on rejection, bonus on full acceptance), pad
        # beyond — always K+1 wide, n_emit says how much is real
        idx = jnp.arange(k + 1)[None, :]
        drafts_p = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), drafts.dtype)], axis=1
        )
        emitted = jnp.where(
            idx < m[:, None],
            drafts_p,
            jnp.where(idx == m[:, None], extra[:, None], pad_id),
        )
        # length cap: live slots may emit positions pos+1..limit-1
        # (the chunk program's pos+2>=limit rule, batched)
        n_emit = jnp.minimum(
            m + 1, jnp.maximum(limit - 1 - pos, 0)
        )
        if eos_id is not None:
            eos_mask = (emitted == eos_id) & (idx < n_emit[:, None])
            has_eos = eos_mask.any(axis=1)
            n_emit = jnp.where(
                has_eos, jnp.argmax(eos_mask, axis=1) + 1, n_emit
            )
        else:
            has_eos = jnp.zeros_like(done)
        n_emit = jnp.where(done, 0, n_emit)
        emitted = jnp.where(idx < n_emit[:, None], emitted, pad_id)
        last = jnp.take_along_axis(
            emitted, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
        )[:, 0]
        new_tok = jnp.where(n_emit > 0, last, tok)
        new_pos = pos + n_emit
        new_done = done | has_eos | (new_pos >= limit - 1)
        # drafts actually USED (cap may truncate below m) — the
        # controller should only credit tokens that shipped
        accepted = jnp.minimum(m, jnp.maximum(n_emit - 1, 0))
        return (
            new_tok, new_pos, new_done, keys, emitted, n_emit,
            accepted,
        )

    def _verify(
        cache, params, tok, pos, done, limit, keys, drafts, draft_len,
        table=None, adapters=None,
    ):
        """One verify forward + acceptance over a bank (`table` None)
        or a page pool, with `_decode_scan`'s trash-routing and its
        trace-time split: paged_verify_step where the decode step
        takes the page kernel (page-native writes), gather /
        dense-verify / scatter-back elsewhere (a verify is a single
        step, so the one view copy is cost-neutral — it exists so
        both programs share one execution strategy per backend)."""
        tokens = jnp.concatenate([tok[:, None], drafts], axis=1)
        if table is None:
            logits, cache = verify_step(
                cfg, params, tokens, cache, pos, mesh=mesh,
                adapters=adapters,
            )
        else:
            # done rows never touch live pages, so page release needs
            # no device dispatch
            table = jnp.where(done[:, None], 0, table)
            if _paged_step_takes_kernel(
                cfg, tok.shape[0], cache, table, mesh
            ):
                logits, cache = paged_verify_step(
                    cfg, params, tokens, cache, table, pos, mesh=mesh,
                    adapters=adapters,
                )
            else:
                view = gather_pool_view(cache, table)
                logits, view = verify_step(
                    cfg, params, tokens, view, pos, mesh=mesh,
                    adapters=adapters,
                )
                cache = scatter_pool_window(
                    cache, view, table, pos, tokens.shape[1]
                )
        return (cache,) + _accept(
            logits, tok, pos, done, limit, keys, drafts, draft_len
        )

    @partial(jax.jit, donate_argnums=(0,))
    def _run_spec(
        cache, params, tok, pos, done, limit, keys, drafts, draft_len,
        abank=None, aidx=None,
    ):
        return _verify(
            cache, params, tok, pos, done, limit, keys, drafts,
            draft_len, adapters=_lora_operand(abank, aidx),
        )

    @partial(jax.jit, donate_argnums=(0,))
    def _run_spec_paged(
        pool, table, params, tok, pos, done, limit, keys, drafts,
        draft_len, abank=None, aidx=None,
    ):
        return _verify(
            pool, params, tok, pos, done, limit, keys, drafts,
            draft_len, table=table, adapters=_lora_operand(abank, aidx),
        )

    return {"dense": _run_spec, "paged": _run_spec_paged}


def _build_admit_programs(cfg, max_len, mesh=None):
    """Admission + prefix-pool programs. Each retraces once per
    prompt/suffix BUCKET (log2(max_len) shapes total); slot/row/start
    are traced scalars so no recompile per slot, row, or prefix
    length. The cache/pool argument is donated: an admission updates
    the bank in place instead of copying it.

    The two cold admissions take an adaptered request's bank and
    adapter slot as optional trailing operands: its prompt K/V must
    come from the ADAPTED projections (RoPE is linear, so the
    pre-rotation delta equals what merged weights would have
    rotated). It bypasses the shared prefix pool entirely — published
    prefixes are base-model K/V by contract, so warm/hit/publish take
    no such operand."""

    def _one_row(abank, aslot):
        if abank is None:
            return None
        return _lora_operand(abank, jnp.full((1,), aslot, jnp.int32))

    @partial(jax.jit, donate_argnums=(0,))
    def _admit_fn(cache, params, prompt, slot, abank=None, aslot=None):
        return prefill_into_slot(
            cfg, params, prompt, cache, slot, mesh=mesh,
            adapters=_one_row(abank, aslot),
        )

    @partial(jax.jit, donate_argnums=(0,))
    def _admit_cold_fn(cache, params, prompt, slot):
        """Full prefill into an exact working row, installed into
        the slot (quantizing iff the bank is int8). Returns the
        row too so the host can publish its prefix."""
        row = prefill_exact_row(cfg, params, prompt, max_len, mesh=mesh)
        return install_exact_row(cache, row, slot), row

    @partial(jax.jit, donate_argnums=(0,))
    def _admit_warm_fn(cache, pool, params, suffix, slot, row, start):
        """Suffix-only prefill: copy pool row `row` (exact K/V of
        the matched prefix) into a working row, run ONLY the
        suffix forward at positions [start, start+S), install."""
        work = pool_take_row(pool, row)
        work = prefill_suffix_row(
            cfg, params, suffix, work, start, mesh=mesh
        )
        return install_exact_row(cache, work, slot), work

    @partial(jax.jit, donate_argnums=(0,))
    def _admit_hit_fn(cache, pool, slot, row):
        """Full-prefix hit: zero prefill FLOPs — install the pool
        row and let the first chunk step recompute the last prompt
        token's logits from the cache (the cold path discards its
        prefill logits the same way)."""
        return install_exact_row(
            cache, pool_take_row(pool, row), slot
        )

    @partial(jax.jit, donate_argnums=(0,))
    def _publish_fn(pool, work, row):
        return pool_put_row(pool, work, row)

    # ---- paged-layout admissions (kv_layout="paged") ----------------
    # Same exact-fp32 working rows, but the install half scatters into
    # the slot's PAGES instead of copying a dense bank row — and a
    # warm admission scatters ONLY the suffix cells (the shared prefix
    # pages are already populated; the table points at them for free).
    # There is no paged "hit" program at all: a full-prefix hit is
    # pure host bookkeeping plus at most one page CoW copy.

    # Each admit program also installs the slot's table row in the
    # SAME dispatch (table.at[slot].set) — a separate _table_row_prog
    # call would add a device round-trip per admission, which lands
    # between other slots' decode chunks and shows up directly in
    # their TPOT. The table is not donated (see the state-scatter
    # comment below: a cancel-time reset may race a pending async
    # host copy).

    @partial(jax.jit, donate_argnums=(0,))
    def _paged_cold_fn(
        pages, table, params, prompt, slot, table_row,
        abank=None, aslot=None,
    ):
        row = prefill_exact_row(
            cfg, params, prompt, max_len, mesh=mesh,
            adapters=_one_row(abank, aslot),
        )
        pages = paged_install_row(
            pages, row, table_row, 0, prompt.shape[0]
        )
        return pages, table.at[slot].set(table_row), row

    @partial(jax.jit, donate_argnums=(0,))
    def _paged_warm_fn(pages, table, pool, params, suffix, slot,
                       table_row, row, start):
        work = pool_take_row(pool, row)
        work = prefill_suffix_row(
            cfg, params, suffix, work, start, mesh=mesh
        )
        pages = paged_install_row(
            pages, work, table_row, start, suffix.shape[0]
        )
        return pages, table.at[slot].set(table_row), work

    @partial(jax.jit, donate_argnums=(0,))
    def _page_copy_fn(pages, src, dst):
        return pool_copy_page(pages, src, dst)

    @partial(jax.jit, donate_argnums=(0,))
    def _paged_cold_hybrid_fn(
        pools, table, params, prompt, slot, table_row, ring_row, p
    ):
        """Window and full layers mixed: one prefill of the bucket,
        then the full layers' cells into the full class and the
        prompt's last `sliding_window` positions (`p` is its true
        length) into the slot's ring."""
        row = prefill_exact_row(cfg, params, prompt, max_len, mesh=mesh)
        pools = paged_install_hybrid(
            cfg, pools, row, table_row, ring_row, p, prompt.shape[0]
        )
        return pools, table.at[slot].set(table_row)

    return {
        "admit": _admit_fn,
        "cold": _admit_cold_fn,
        "warm": _admit_warm_fn,
        "hit": _admit_hit_fn,
        "publish": _publish_fn,
        "paged_cold": _paged_cold_fn,
        "paged_warm": _paged_warm_fn,
        "page_copy": _page_copy_fn,
        "paged_cold_hybrid": _paged_cold_hybrid_fn,
    }


# ---------------------------------------------------------------------------
# Device-resident slot state. The [B]-vector state lives on device
# between dispatches; these scatter programs are the ONLY way host
# events (admission, cancel, failover re-key) reach it. `slot` and the
# scalar values are traced, so each program compiles once per bank
# shape — never per slot or per request. The buffers are tiny, so
# nothing here donates: a cancel may land while a dispatch's outputs
# still have a pending copy_to_host_async, and donating such a buffer
# would race the copy.


@jax.jit
def _state_admit_prog(tok, pos, done, limit, keys,
                      slot, tok_v, pos_v, limit_v, key_v):
    return (
        tok.at[slot].set(tok_v),
        pos.at[slot].set(pos_v),
        done.at[slot].set(False),
        limit.at[slot].set(limit_v),
        keys.at[slot].set(key_v),
    )


@jax.jit
def _state_admit_block_prog(blk, msk, prev, pos, done, limit,
                            slot, blk_v, msk_v, pos_v, limit_v):
    """`_state_admit_prog` for a slot whose state is a diffusion
    block. A request's first block owes the pool nothing: the
    prefill installed every cell before it."""
    return (
        blk.at[slot].set(blk_v),
        msk.at[slot].set(msk_v),
        prev.at[slot].set(-1),
        pos.at[slot].set(pos_v),
        done.at[slot].set(False),
        limit.at[slot].set(limit_v),
    )


@jax.jit
def _state_cancel_prog(done, slot):
    return done.at[slot].set(True)


@jax.jit
def _state_frontier_prog(frontier, slot, val):
    """Admission scatter for the partial write frontier ([B] int32,
    minted only when prefill_chunk > 0). Release paths need no
    scatter: a retired slot's stale frontier is dead — the interleaved
    dispatcher only reads entries it set itself at admission, and the
    pf chunk program only writes the slot it is prefilling."""
    return frontier.at[slot].set(val)


@jax.jit
def _state_adapt_prog(adapt, slot, val):
    """Admission scatter for the per-slot adapter-index vector (only
    minted when multi-adapter serving is on). Release paths need no
    scatter: a done row's stale index gathers harmlessly — its
    output is discarded and its frozen rewrites are dead by the
    position mask (dense) or trash-routed (paged)."""
    return adapt.at[slot].set(val)


# page-table scatters (kv_layout="paged"): the device table [B, P] is
# part of the resident state — full-hit admissions set a whole row,
# CoW patches one entry. Release paths need NO scatter: the chunk and
# verify programs route done rows through the trash page themselves.
# Like the state scatters above, nothing donates: a scatter may land
# while a dispatch's outputs still have a pending async host copy.


@jax.jit
def _table_row_prog(table, slot, vals):
    return table.at[slot].set(vals)


@jax.jit
def _table_entry_prog(table, slot, idx, val):
    return table.at[slot, idx].set(val)


def _to_host(*arrays) -> Tuple[np.ndarray, ...]:
    """THE designated fetch helper: the only place in this module a
    device array may materialize on the host. Blocking lives here by
    design — in async mode the copies were started with
    copy_to_host_async() at dispatch, so this completes them instead
    of issuing fresh synchronous D2H transfers. np.array (copy, not
    view): the results become the writable host mirrors that
    _admit/cancel mutate in place."""
    return tuple(np.array(a) for a in arrays)


def _start_host_copy(arrays) -> None:
    """Begin non-blocking D2H copies on a dispatch's outputs; the
    harvest's _to_host then completes them after the host has had the
    device span to do real work."""
    for a in arrays:
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            start()


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-not-harvested device step: the output
    arrays (host copies already in flight) plus the host-side context
    needed to turn them into events at harvest time."""

    kind: str                       # "chunk" | "spec" | "blocks"
    arrays: tuple                   # device outputs, fetch order
    dispatched_at: float            # perf_counter at enqueue
    old_pos: Optional[np.ndarray] = None    # chunk: pos at dispatch
    dlens: Optional[np.ndarray] = None      # spec: drafted lengths
    was_live: Optional[np.ndarray] = None   # spec: live at dispatch
    version: int = 0                # weight version at dispatch
    # interleaved dispatch: which slots were MID-PREFILL when it was
    # built. Their fetched done=True is the freeze, not a finish, and
    # their fetched key drifted (the scan splits every row's key);
    # harvest must neither finish them nor let the drift reach the
    # key mirror the journal reads.
    pf_mask: Optional[np.ndarray] = None


def _spanned_build(init):
    """The constructor as the span `engine.build` (pools on the
    device, programs bound; counts `slots`, `max_len`, `compile_s`).
    `serving/` has no entry point of its own, so this is also where a
    server built by a user's script starts counting its compilations
    (common/trace.py `watch_compiles`); the build's own open the
    totals that `step` goes on adding to."""

    @wraps(init)
    def build(self, *args, **kwargs):
        trace.watch_compiles()
        seconds, programs = trace.compiled()
        with trace.span("engine.build") as sp:
            init(self, *args, **kwargs)
            spent, compiled = trace.compiled()
            self._stat_compile_s += spent - seconds
            self._stat_compilations += compiled - programs
            sp.set(
                slots=self.n_slots, max_len=self.max_len,
                compile_s=spent - seconds,
            )

    return build


class ContinuousBatcher:
    """Greedy/sampling rollouts over a slot bank.

    generate_all(prompts) -> list of generated continuations (eos
    included when hit), in submission order. `params` may be any
    llama/GPT-family pytree models/decode.py serves.
    """

    @_spanned_build
    def __init__(
        self,
        cfg,
        params,
        n_slots: int = 8,
        max_len: int = 512,
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_id: Optional[int] = None,
        pad_id: int = 0,
        chunk: int = 8,   # steps per dispatch; see _next_chunk_len
        seed: int = 0,
        kv_quant: bool = False,  # int8 KV cache (~2x slots per HBM)
        prefix_cache_rows: int = 0,  # 0 disables the prefix cache
        prefix_block: int = 16,      # prefix match granularity (tokens)
        spec_draft_len: int = 0,     # speculative draft width K (0 = off)
        spec_ngram_max: int = 3,     # longest suffix n-gram the drafter tries
        spec_ngram_min: int = 1,     # shortest n-gram fallback
        spec_accept_threshold: float = 0.5,  # EMA acceptance to keep drafting
        spec_probe_interval: int = 32,  # rounds between disabled-slot probes
        chaos=None,                  # serving/chaos.py FaultInjector
        chaos_tag: str = "engine",   # this engine's tag in fault plans
        async_depth: int = 1,        # 0 = harvest in the same step()
        kv_layout: str = "dense",    # "dense" bank | "paged" pool
        page_size: int = 0,          # cells per page (0 = auto pow2)
        n_pages: int = 0,            # pool size (0 = dense-equivalent)
        swap_headroom: int = 1,      # free pages the scheduler keeps
        mesh_spec=None,              # tp degree | {"tp": n} | MeshSpec
        replica_role: str = "colocated",  # | "prefill" | "decode"
        weight_refresh_mode: str = "defer",  # | "live" | "raise"
        weight_refresh_replay: bool = True,  # live mode: replay slots
        adapter_registry=None,       # serving/adapters.AdapterRegistry
        adapter_cache_slots: int = 8,  # device adapter bank slots (LRU)
        prefill_chunk: int = 0,  # tokens of prefill per interleaved
                                 # dispatch (0 = blocking admission)
        kv_tier_bytes: int = 0,  # host-DRAM KV tier capacity (0 = off)
        swap_to_host: bool = True,   # preempted runs demote, not drop
        kv_tier_promote: str = "always",  # | "swap_only" | "never"
        kv_checksums: int = 0,   # 1 = content-verify KV in transit
        weight_quant: str = "none",  # | "int8" | "int8_stochastic":
                                 # per-block int8 matmul weights
        denoising_steps: int = 0,    # a block-diffusion model's steps a
                                 # block (0 = one position a forward)
    ):
        if eos_id is not None and eos_id == pad_id:
            raise ValueError(
                "eos_id and pad_id must differ: the pad emitted by "
                "finished slots would re-trigger EOS detection"
            )
        if spec_draft_len < 0:
            raise ValueError(
                f"spec_draft_len must be >= 0, got {spec_draft_len}"
            )
        if spec_draft_len >= max_len:
            raise ValueError(
                f"spec_draft_len {spec_draft_len} must be < max_len "
                f"{max_len}"
            )
        if async_depth not in (0, 1):
            raise ValueError(
                f"async_depth must be 0 (sync) or 1 (one-deep "
                f"pipeline), got {async_depth}"
            )
        if replica_role not in ("colocated", "prefill", "decode"):
            raise ValueError(
                f"replica_role must be 'colocated', 'prefill' or "
                f"'decode', got {replica_role!r}"
            )
        if prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {prefill_chunk}"
            )
        if kv_tier_bytes < 0:
            raise ValueError(
                f"kv_tier_bytes must be >= 0, got {kv_tier_bytes}"
            )
        if kv_tier_promote not in ("always", "swap_only", "never"):
            raise ValueError(
                f"kv_tier_promote must be 'always', 'swap_only' or "
                f"'never', got {kv_tier_promote!r}"
            )
        if kv_checksums not in (0, 1):
            raise ValueError(
                f"kv_checksums must be 0 (off) or 1 (verify KV in "
                f"transit), got {kv_checksums}"
            )
        if weight_quant not in ("none", "int8", "int8_stochastic"):
            raise ValueError(
                f"weight_quant must be 'none', 'int8' or "
                f"'int8_stochastic', got {weight_quant!r}"
            )
        _check_positional_capacity(cfg, max_len)
        _refuse_unserved(
            cfg,
            prefix_cache_rows=prefix_cache_rows > 0,
            kv_tier_bytes=kv_tier_bytes > 0,
            replica_role=replica_role != "colocated",
            spec_draft_len=spec_draft_len > 0,
            adapter_registry=adapter_registry is not None,
            weight_quant=weight_quant != "none",
            mesh_spec=(
                mesh_spec is not None and _parse_mesh_tp(mesh_spec) > 1
            ),
            kv_quant=bool(kv_quant),
            prefill_chunk=prefill_chunk > 0,
        )
        # ---- generation by diffusion over blocks ------------------------
        # a slot's state is a block and a forward does not yield one
        # token: ONE more chunk program (`_build_diffusion_program`)
        # behind step / _dispatch_chunk / _harvest, over the paged pool
        self._block = _block_of(cfg)
        self._denoise_steps = 0
        if self._block:
            self._denoise_steps = denoising_steps or self._block
            refused = [
                name for name, on in (
                    ("kv_layout='dense'", kv_layout != "paged"),
                    ("temperature > 0", temperature > 0.0),
                    ("eos_id", eos_id is not None),
                    (f"denoising_steps outside 1..{self._block}",
                     not 1 <= self._denoise_steps <= self._block),
                    (f"max_len not a multiple of {self._block}",
                     max_len % self._block != 0),
                ) if on
            ]
            if refused:
                raise ValueError(
                    "a model that generates by diffusion over blocks of "
                    f"{self._block} positions is served greedy, with no "
                    "end-of-sequence token, over the paged pool; not "
                    "with " + ", ".join(refused)
                )
        elif denoising_steps:
            raise ValueError(
                "denoising_steps is a block-diffusion model's "
                "(cfg.block_length > 0)"
            )
        # ---- serving mesh (GSPMD tensor slice) --------------------------
        # tp=1 (or the knob unset) keeps mesh=None: the compiled
        # programs are then literally the single-device ones (the mesh
        # joins every program-cache key, and constrain() is the
        # identity under mesh=None), so the parity contract for the
        # default path is structural, not merely numerical.
        self.mesh = None
        self.mesh_tp = 1
        if mesh_spec is not None:
            tp = _parse_mesh_tp(mesh_spec)
            n_kv = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
            # validate even for tp=1 so a bad knob fails loudly here
            serving_mesh_spec(tp, n_kv_heads=n_kv)
            self.mesh_tp = tp
            if tp > 1:
                self.mesh = serving_mesh(tp, n_kv_heads=n_kv)
        # ---- elastic state ----------------------------------------------
        # The constructed tp is the grow-back target after a shrink;
        # weight refreshes are version-tagged (the version joins every
        # program-cache key so no stale closure can serve old weights)
        # and stage mid-drain instead of silently mixing policies.
        self._full_tp = self.mesh_tp
        if weight_refresh_mode not in ("live", "defer", "raise"):
            raise ValueError(
                f"weight_refresh_mode must be 'live', 'defer' or "
                f"'raise', got {weight_refresh_mode!r}"
            )
        self.weight_refresh_mode = weight_refresh_mode
        self.weight_refresh_replay = weight_refresh_replay
        self._weight_version = 0
        self._staged_params = None
        self._bound_keys: List[Any] = []  # (cache, key) pairs in use
        self._elastic_resize = {"shrink": 0, "grow": 0}
        self._elastic_refresh = {
            "committed": 0, "deferred": 0, "rolled_back": 0,
        }
        self._elastic_downtime_ms = 0.0
        self._elastic_replayed = 0
        self.cfg = cfg
        # ---- int8 weight quantization (ops/quantization.py) -------------
        # weight_quant="int8" re-stores the large matmul weights as
        # per-block int8 + f32 scales AT INSTALL TIME (here and at
        # every committed refresh); decode's matmuls dequant-fuse via
        # matmul_any. "none" skips quantization entirely — the served
        # tree, the compiled programs and every program-cache key are
        # byte-identical to pre-quantization builds.
        self.weight_quant = weight_quant
        self._wq_seed = seed
        self._wq_stats = {"leaves": 0, "skipped": 0}
        # weight refreshes arrive as DENSE host trees; they validate
        # against the pre-quantization skeleton, not the (possibly
        # QuantizedWeight-bearing) served tree
        self._refresh_skeleton = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                tuple(x.shape), jnp.dtype(x.dtype)
            ),
            params,
        )
        self.params = self._shard_params(self._quantize_params(params))
        self.n_slots = n_slots
        self.max_len = max_len
        self.max_new = max_new_tokens
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.chunk = chunk
        self.chaos = chaos
        self.chaos_tag = chaos_tag
        self._step_no = 0
        # KV integrity (serving/health.py): content checksums over
        # host-side KV in transit. Host-bytes bookkeeping only — with
        # the knob at 0 (and no tier/handoff stamped) every device
        # path is bit-exact legacy and no new program is ever traced.
        self.kv_checksums = int(kv_checksums)
        self._integrity_checks = 0
        self._integrity_quarantines = 0
        # MPMD phase split: "prefill" admits (admission IS the
        # prefill — the admit programs write KV cells 0..p-1
        # synchronously) but never dispatches a decode step; finished
        # prefills queue in _prefill_ready for the scheduler to export
        # via serving/handoff.py. "decode" is advisory routing state —
        # stepping is identical to colocated.
        self.replica_role = replica_role
        self._prefill_ready: List[_Request] = []
        # knobs reset() needs to rebuild device state after a crash
        self._kv_quant = kv_quant
        self._prefix_rows = prefix_cache_rows
        self._prefix_block = prefix_block
        self._spec_knobs = (
            spec_ngram_max, spec_ngram_min,
            spec_accept_threshold, spec_probe_interval,
        )
        # engine key only SEEDS per-request keys (one split per
        # admission, on the host: see the `key` property); sampling
        # itself runs on the per-slot keys below
        self.key = jax.random.PRNGKey(seed)
        self.slot_key = np.zeros((n_slots, 2), np.uint32)
        # the slot bank over-allocates by the draft width: a verify
        # dispatch always writes K+1 cells at [pos, pos+K], and a slot
        # near its cap (pos up to max_len-2) must not have that window
        # clamp back onto valid cells (dynamic_update_slice clamps the
        # start; the overflow cells sit at positions no valid query
        # ever attends, so they are dead by the position mask). With
        # spec_draft_len=0 the bank is exactly max_len — today's
        # shapes, today's programs, bit-exact behavior.
        if kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"kv_layout must be 'dense' or 'paged', got "
                f"{kv_layout!r}"
            )
        self.kv_layout = kv_layout
        self._paged = kv_layout == "paged"
        # window and full layers mixed: two classes of pages
        self._hybrid = self._paged and bool(getattr(cfg, "hybrid", False))
        # one latent row a token and layer in place of k and v: a
        # third class of pool, under the one allocator and table
        self._latent = self._paged and bool(getattr(cfg, "latent", False))
        bank_len = max_len + spec_draft_len
        if self._paged:
            # auto page size: the largest power of two <= 16 dividing
            # the bank length (and the prefix block, so a matched
            # prefix is always a whole number of pages)
            if page_size <= 0:
                page_size = 16
                while page_size > 1 and (
                    bank_len % page_size
                    or (
                        prefix_cache_rows > 0
                        and prefix_block % page_size
                    )
                ):
                    page_size //= 2
            if bank_len % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide max_len + "
                    f"spec_draft_len = {bank_len}: a slot's logical "
                    "cells must map onto whole pages"
                )
            if self._block and page_size % self._block:
                raise ValueError(
                    f"page_size {page_size} must be a multiple of the "
                    f"diffusion block of {self._block} positions: a "
                    "block never straddles a page"
                )
            if prefix_cache_rows > 0 and prefix_block % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide prefix_block "
                    f"{prefix_block}: shared prefixes must cover "
                    "whole pages or sharing cannot be copy-free"
                )
            per_slot = bank_len // page_size
            if n_pages <= 0:
                # dense-equivalent capacity (+ the trash page): same
                # HBM as the dense bank, oversubscription comes from
                # setting n_pages lower
                n_pages = n_slots * per_slot + 1
            if n_pages < per_slot + 1:
                raise ValueError(
                    f"n_pages {n_pages} cannot back a single maximal "
                    f"request ({per_slot} pages + the trash page)"
                )
            self.page_size = page_size
            self.n_pages = n_pages
            self.swap_headroom = max(0, swap_headroom)
            self._pages_per_slot = per_slot
            self.allocator = PageAllocator(n_pages, page_size)
            if self._hybrid:
                self.page_pool = init_hybrid_pools(
                    cfg, n_pages, self._mint_window_class(), page_size
                )
            else:
                self.page_pool = self._shard_bank(
                    init_page_pool(
                        cfg, n_pages, page_size, quant=kv_quant
                    )
                )
            # all rows start on the trash page (page 0); after that
            # the programs trash-route done rows on their own, so the
            # host only ever scatters rows at admission/CoW
            self._table = self._replicate(
                jnp.zeros((n_slots, per_slot), jnp.int32)
            )
            self._slot_pages: List[List[int]] = [
                [] for _ in range(n_slots)
            ]
            # published radix row -> its ref-counted page run
            self._row_pages: Dict[int, List[int]] = {}
            self._swap_preemptions = 0
            self._swap_resumes = 0
            self.cache = None
        else:
            self.cache = self._shard_bank(
                init_kv_cache(cfg, n_slots, bank_len, quant=kv_quant)
            )
        # what the newest harvested dispatch routed to each expert,
        # and the window class's counters of the current step
        self._moe_pairs: Optional[np.ndarray] = None
        self._moe_steps = 0
        self._moe_touched = 0
        self._moe_held_total = 0      # /metrics: the held-pairs share
        self._moe_routed_total = 0
        self._latent_cells = 0
        self._window_freed_this_step = 0
        # a block-diffusion model: the harvested dispatch's live
        # slot-forwards, those that carried a finished block, blocks
        # finished, ids handed to streams and K/V cells read; their
        # running totals (/metrics); and, where
        # `record_blocks` is on, every dispatch's raw record
        # (`block_trajectories`)
        self._diff = None
        self._diff_forwards_total = 0
        self._diff_tokens_total = 0
        self.record_blocks = False
        self.block_log: List[tuple] = []
        # ---- multi-adapter LoRA serving (serving/adapters.py) -----------
        # One stacked device bank whose slot 0 is the permanent zero
        # adapter; every request gathers its slot's A/B slices inside
        # the SAME compiled programs, so heterogeneous-adapter traffic
        # batches through one base-model forward. Leaving the registry
        # unset keeps every structure — _dev, program-cache keys,
        # admission paths — byte-identical to the adapterless engine.
        self.adapter_registry = adapter_registry
        self._adapter_cache = None
        if adapter_registry is not None:
            # GPT's fused qkv has no per-target bank — fail at
            # construction, not from inside a compiled program
            _check_adapters(cfg, adapter_registry)
            self._adapter_cache = DeviceAdapterCache(
                cfg,
                adapter_registry,
                adapter_cache_slots,
                place=self._adapter_bank_place,
            )
        # ---- interleaved chunked prefill --------------------------------
        # prefill_chunk > 0 splits cold admissions into bounded chunks
        # co-scheduled with decode: _admit installs the slot FROZEN
        # (device done=True, zero tokens emitted) with a partial write
        # frontier, and each dispatch fuses up to prefill_chunk prompt
        # tokens with the usual decode scan in ONE compiled program
        # until the frontier reaches the prompt end and the slot flips
        # to decoding. prefill_chunk=0 keeps the blocking path — and
        # every structure below except these tiny host vectors —
        # bit-exact (the parity oracle).
        self._prefill_chunk = prefill_chunk
        self._prefilling = np.zeros(n_slots, bool)
        self._frontier = np.zeros(n_slots, np.int32)
        # prefill-role only: slots whose prefill is COMPLETE and
        # parked for export. Blocking prefill-role engines never
        # dispatch, so parked slots could stay device-live; the
        # interleaved engine keeps dispatching while other slots
        # stream in, so parked slots must be frozen on device and
        # recognized at harvest (their done=True is the park, not a
        # finish — releasing their pages would kill the export)
        self._parked = np.zeros(n_slots, bool)
        self._admission_stall_ms = 0.0     # time _admit blocked the loop
        self._prefill_chunks_total = 0     # interleaved chunks dispatched
        # host MIRRORS of the slot state (tiny [B] vectors). The truth
        # lives on device in self._dev; these track it so admission
        # and scheduler decisions (_next_chunk_len, free_slots,
        # live_request_keys) never block on a device read. Mirrors are
        # written by _admit/cancel (whose values are host-known) and
        # refreshed from each dispatch's fetched outputs in _harvest.
        self.tok = np.full(n_slots, pad_id, np.int32)
        self.pos = np.zeros(n_slots, np.int32)
        self.limit = np.zeros(n_slots, np.int32)
        self.done = np.ones(n_slots, bool)   # all free initially
        # per-slot adapter-bank index (0 = the zero adapter); joins
        # the device state only when multi-adapter serving is on
        self.adapt = np.zeros(n_slots, np.int32)
        # a diffusion block a slot: its ids and which are still masked
        # (`pos` is then the block's first position)
        self.blk = np.zeros((n_slots, self._block), np.int32)
        self.msk = np.zeros((n_slots, self._block), bool)
        self.async_depth = async_depth
        self._dev = self._device_state()
        # the one dispatched-but-unharvested device step (async mode)
        self._inflight: Optional[_Inflight] = None
        # step-latency micro-stats (metrics.py exposition): host work,
        # time blocked on the device, and how much device span the
        # host work hid (the overlap the async mode exists to buy)
        self._stat_host_ms = 0.0
        self._stat_wait_ms = 0.0
        self._stat_span_ms = 0.0
        self._stat_overlap_ms = 0.0
        self._stat_dispatches = 0
        # what this engine's build and steps spent on jax's compile
        # path, and the programs they compiled or read back
        self._stat_compile_s = 0.0
        self._stat_compilations = 0
        self._wait_this_step = 0.0   # s blocked on the device, this step
        self._overlap_this_step = 0.0  # s of device span hidden, this step
        self._admit_this_step = 0.0  # s inside _admit, this step
        # extent of the last engine.step span: the scheduler's
        # straggler EWMA reads it instead of timing step() again
        self.last_step_s = 0.0
        self.slot_req: List[Optional[_Request]] = [None] * n_slots
        self._queue: deque = deque()
        # ledger: idx -> request, plus the order generate_all returns.
        # A dict (not a list) so the serving path can retire() finished
        # requests individually without shifting later indices.
        self._requests: Dict[int, _Request] = {}
        # submitted, not yet returned — an insertion-ordered dict used
        # as an ordered set: retire() must be O(1), not an O(n) list
        # scan, or a long-lived serving engine degrades linearly in
        # requests ever served
        self._pending: Dict[int, None] = {}
        self._next_idx = 0

        # ---- host-DRAM KV tier (serving/kv_tier.py) ---------------------
        # The rung between eviction and recompute: evicted published
        # prefixes and preempted page runs demote to host DRAM via
        # async D2H and promote back over PCIe instead of paying a
        # cold prefill or a full replay. kv_tier_bytes=0 keeps every
        # path below bit-exact (no tier object, no new programs).
        self.kv_tier = None
        self._tier_swap = bool(swap_to_host)
        self._tier_promote = kv_tier_promote
        if kv_tier_bytes > 0:
            self.kv_tier = _kv_tier.HostKVTier(
                kv_tier_bytes,
                block=prefix_block,
                chaos=chaos,
                chaos_tag=f"{chaos_tag}#kvtier",
                checksums=bool(kv_checksums),
            )

        # ---- admission-time prefix cache --------------------------------
        # A radix tree over block-quantized prompt prefixes whose rows
        # live in a second, exact-dtype KV bank beside the slot bank.
        # On admission the longest cached block-aligned prefix is
        # installed into the slot with one compiled copy and only the
        # SUFFIX is prefilled; the request's own aligned prefix is
        # published back for the next arrival. See prefix_cache.py for
        # the design note vs vLLM page tables.
        self.prefix_cache: Optional[RadixPrefixCache] = None
        self.pool = None
        # pool row pinned per slot while its request is in flight
        self._slot_row: List[Optional[int]] = [None] * n_slots
        if prefix_cache_rows > 0:
            # paged: eviction of a published prefix must drop the
            # run's page refs, or evicted prefixes leak pool pages
            self.prefix_cache = RadixPrefixCache(
                prefix_cache_rows,
                block=prefix_block,
                on_evict=(
                    self._on_prefix_evict
                    if (self._paged or self.kv_tier is not None)
                    else None
                ),
            )
            # exact dtype even when the slot bank is int8: install
            # re-quantizes, which keeps warm admissions byte-identical
            # to cold ones (models/decode.py pool primitives)
            self.pool = self._shard_bank(
                init_kv_cache(cfg, prefix_cache_rows, max_len)
            )

        # ---- speculative decoding ---------------------------------------
        # host drafter + adaptive controller (serving/speculative.py);
        # the verify program is cached like the chunk program — one
        # trace per (config, knobs, K), shared across engines
        self.spec: Optional[SpeculativeDecoder] = None
        self._run_spec = None
        if spec_draft_len > 0:
            self.spec = SpeculativeDecoder(
                n_slots,
                spec_draft_len,
                ngram_max=spec_ngram_max,
                ngram_min=spec_ngram_min,
                threshold=spec_accept_threshold,
                probe_interval=spec_probe_interval,
            )
        self.spec_draft_len = spec_draft_len

        # sampling knobs survive as engine state: an elastic resize or
        # a weight refresh re-runs the program-cache lookups
        # (_bind_programs) with the same sampling tuple under a new
        # mesh / weight-version key
        self._sampling = (temperature, top_k, top_p)
        self._bind_programs()
        self._probe_kernel_path()

    def _mint_window_class(self) -> int:
        """Host side of the second class of pages, where window and
        full layers mix (the full class is the one there was:
        `self.allocator`, `_slot_pages`, `_table`): its allocator and
        the slots' rings (`self.rings`, serving/paged_kv.WindowRings),
        sized so that every slot's ring is whole at once (and the
        trash page): a ring never waits for a page. Returns the
        class's page count."""
        probe = WindowRings(
            PageAllocator(2, self.page_size), 0,
            self.cfg.sliding_window, self.chunk,
        )
        n_win = self.n_slots * probe.ring_pages + 1
        self.allocator_win = PageAllocator(n_win, self.page_size)
        self.rings = WindowRings(
            self.allocator_win, self.n_slots,
            self.cfg.sliding_window, self.chunk,
        )
        return n_win

    def _bind_programs(self) -> None:
        """(Re)bind the jitted programs for the CURRENT (cfg, sampling
        knobs, mesh, weight version). Called at construction, again by
        serving/elastic.py after a mesh resize (the mesh is in every
        cache key, so a resized engine naturally selects freshly
        specialized programs), and by a committed weight refresh (the
        version component retires the prior version's entries so no
        stale closure can ever serve old weights)."""
        cfg = self.cfg
        temperature, top_k, top_p = self._sampling
        version = self._weight_version
        self._bound_keys = []
        if self.spec is not None:
            key = (
                (cfg, self.pad_id, self.eos_id, temperature, top_k,
                 top_p, self.spec_draft_len, self.mesh, version)
                + _kernel_cache_tag() + self._adapter_tag() + self._wq_tag()
            )
            self._bound_keys.append((_SPEC_PROGRAMS, key))
            self._run_spec = _cached_program(
                _SPEC_PROGRAMS,
                # graftlint: allow(JIT-003) reason=hashable tuple literal assigned above and recorded in _bound_keys so a weight refresh can retire the prior version's entries
                key,
                lambda: _build_spec_program(
                    cfg, self.pad_id, self.eos_id, temperature,
                    top_k, top_p, mesh=self.mesh,
                ),
            )[self.kv_layout]
        key = (
            (cfg, self.pad_id, self.eos_id, temperature, top_k, top_p,
             self.mesh, version)
            + _kernel_cache_tag() + self._adapter_tag() + self._wq_tag()
        )
        if self._block:
            # a step there is a forward over a block: its own program,
            # keyed by the denoising steps beside the sampling knobs
            key = key + ("blocks", self._denoise_steps)
        self._bound_keys.append((_CHUNK_PROGRAMS, key))
        self._run_chunk = _cached_program(
            _CHUNK_PROGRAMS,
            # graftlint: allow(JIT-003) reason=hashable tuple literal assigned above and recorded in _bound_keys so a weight refresh can retire the prior version's entries
            key,
            lambda: _build_diffusion_program(
                cfg, self._denoise_steps
            ) if self._block else _build_chunk_program(
                cfg, self.pad_id, self.eos_id, temperature, top_k,
                top_p, mesh=self.mesh,
            ),
        )[self.kv_layout]
        # interleaved chunked-prefill variant: bound ONLY when the
        # knob is on, so prefill_chunk=0 engines add zero cache keys
        # and keep the pre-PR key population bit-exact
        self._run_pf = None
        if self._prefill_chunk > 0:
            key = (
                (cfg, self.pad_id, self.eos_id, temperature, top_k,
                 top_p, self.mesh, version, "prefill")
                + _kernel_cache_tag() + self._adapter_tag() + self._wq_tag()
            )
            self._bound_keys.append((_CHUNK_PROGRAMS, key))
            self._run_pf = _cached_program(
                _CHUNK_PROGRAMS,
                # graftlint: allow(JIT-003) reason=hashable tuple literal assigned above and recorded in _bound_keys so a weight refresh can retire the prior version's entries
                key,
                lambda: _build_pf_chunk_program(
                    cfg, self.pad_id, self.eos_id, temperature,
                    top_k, top_p, mesh=self.mesh,
                ),
            )[self.kv_layout]
        key = (
            (cfg, self.max_len, self.mesh, version)
            + _kernel_cache_tag() + self._adapter_tag() + self._wq_tag()
        )
        self._bound_keys.append((_ADMIT_PROGRAMS, key))
        admit = _cached_program(
            _ADMIT_PROGRAMS,
            # graftlint: allow(JIT-003) reason=hashable tuple literal assigned above and recorded in _bound_keys so a weight refresh can retire the prior version's entries
            key,
            lambda: _build_admit_programs(
                cfg, self.max_len, mesh=self.mesh
            ),
        )
        self._admit_fn = admit["admit"]
        self._admit_cold_fn = admit["cold"]
        self._admit_warm_fn = admit["warm"]
        self._admit_hit_fn = admit["hit"]
        self._publish_fn = admit["publish"]
        self._paged_cold_fn = admit["paged_cold"]
        self._paged_warm_fn = admit["paged_warm"]
        self._page_copy_fn = admit["page_copy"]
        self._paged_cold_hybrid_fn = admit["paged_cold_hybrid"]

    def _wq_tag(self) -> tuple:
        """Program-cache key component for weight quantization: the
        mode string when on (a quantized tree traces different
        programs — QuantizedWeight operands, fused dequant). Empty
        when weight_quant="none", so default-path keys stay
        byte-identical to pre-quantization builds — the program-cache
        census in tests/test_serving_weight_quant.py locks this."""
        if self.weight_quant == "none":
            return ()
        return ("wq", self.weight_quant)

    def _adapter_tag(self) -> tuple:
        """Program-cache key component for multi-adapter serving: the
        bank's static shape signature (slot count and max rank change
        every traced program). Empty when adapters are off, so
        adapterless keys stay byte-identical to pre-adapter builds —
        and keep sharing their cached programs."""
        if self._adapter_cache is None:
            return ()
        c = self._adapter_cache
        return ("adapters", c.cache_slots, c.max_rank)

    def _adapter_args(self) -> dict:
        """The programs' optional adapter operands, by keyword: the
        stacked device bank and the per-slot adapter-index vector.
        Empty when multi-adapter serving is off — the programs are
        then called, and traced, without them."""
        if self._adapter_cache is None:
            return {}
        return {
            "abank": self._adapter_cache.bank,
            "aidx": self._dev["adapt"],
        }

    def _probe_kernel_path(self) -> None:
        """Which attention body the per-token decode step traced into
        its program: "kernel" (Pallas paged-attention, shard_mapped
        over "tp" when mesh_tp > 1) or "reference" (XLA gather +
        softmax). Decided with shape probes — use_kernel only
        inspects shapes/dtypes, so ShapeDtypeStructs suffice — at
        construction and re-decided after an elastic resize (the
        per-shard head gates re-evaluate at the new tp). Surfaced via
        /healthz and the serving metrics so bench contracts can
        assert which path a replica actually runs."""
        self.kernel_path = "reference"
        if not self._paged:
            return
        if _paged_step_takes_kernel(
            self.cfg, self.n_slots, self.page_pool, self._table,
            self.mesh,
        ):
            self.kernel_path = "kernel"
        elif jax.default_backend() == "tpu":
            logger.warning(
                "paged decode takes the XLA gather reference on this "
                "TPU: ops/paged_attention.supports() refuses the "
                "shapes (heads=%d kv_heads=%d head_dim=%d page_size=%d "
                "tp=%d latent_width=%d) or attn_impl pins the reference",
                self.cfg.n_heads,
                getattr(self.cfg, "n_kv_heads", self.cfg.n_heads),
                self.cfg.head_dim, self.page_size, self.mesh_tp,
                self.cfg.latent_width if self._latent else 0,
            )

    # -- weight quantization -----------------------------------------------

    def _quantize_params(self, params):
        """Install-time int8 weight quantization — the ONE designated
        quantize site in serving/ (graftlint QUANT-001). Each matmul
        weight [.., K, O] re-stores OUTPUT-MAJOR as q8 int8 [.., O, K]
        + s8 f32 [.., O, K/block] (blocks along the contraction dim;
        see the layout note in ops/quantization.py). Idempotent:
        already-quantized leaves pass through untouched, so an elastic
        resize resharding the served tree never requantizes — the
        exact bits move to the new mesh. weight_quant="none" is the
        identity (same object, not a copy)."""
        if self.weight_quant == "none":
            return params
        if not isinstance(params, dict) or "layers" not in params:
            return params
        stochastic = self.weight_quant == "int8_stochastic"
        leaves = skipped = 0

        lay = dict(params["layers"])
        targets = [
            ("layers", name, salt)
            for salt, name in enumerate(sorted(lay))
            if name in _WQ_LAYER_WEIGHTS
        ]
        head = params.get("lm_head")
        if isinstance(head, dict) and "weight" in head:
            # untied unembed [D, V]: the single biggest weight read of
            # a decode step. Tied heads never reach here (no lm_head
            # key) — the gather keeps the dense embedding table.
            head = dict(head)
            targets.append(("lm_head", "weight", len(lay)))
        for group, name, salt in targets:
            w = lay[name] if group == "layers" else head[name]
            if isinstance(w, QuantizedWeight):
                leaves += 1  # resize/reshard path: keep the bits
                continue
            shape = tuple(w.shape)
            blk = weight_quant_block(shape[-2]) if len(shape) > 1 else 0
            if blk == 0:
                skipped += 1
                continue
            *lead, k_dim, o_dim = shape
            wt = jnp.swapaxes(jnp.asarray(w, jnp.float32), -1, -2)
            flat = wt.reshape((-1, k_dim))
            if stochastic:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(self._wq_seed), salt
                )
                q, s = stochastic_round_int8(flat, key, blk)
            else:
                q, s = quantize_int8(flat, blk)
            q = q.reshape(tuple(lead) + (o_dim, k_dim))
            s = s.reshape(tuple(lead) + (o_dim, k_dim // blk))
            leaves += 1
            qw = QuantizedWeight(q, s, blk)
            if group == "layers":
                lay[name] = qw
            else:
                head[name] = qw
        out = dict(params)
        out["layers"] = lay
        if isinstance(head, dict) and "weight" in head:
            out["lm_head"] = head
        self._wq_stats = {"leaves": leaves, "skipped": skipped}
        return out

    def weight_bytes_device(self) -> int:
        """Served-weight bytes resident PER CHIP: each leaf's local
        shard shape (the full shape when replicated or meshless) times
        its itemsize. THE headline this PR moves — decode streams
        these bytes from HBM every step."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.params):
            shape = tuple(getattr(leaf, "shape", ()))
            sh = getattr(leaf, "sharding", None)
            if self.mesh is not None and sh is not None:
                try:
                    shape = tuple(sh.shard_shape(shape))
                except Exception:  # graftlint: allow(EXC-001) reason=telemetry fallback: a leaf whose sharding cannot express a shard shape (e.g. host-resident during a refresh window) counts its full bytes rather than failing the stats pump
                    pass
            n = 1
            for d in shape:
                n *= int(d)
            total += n * jnp.dtype(leaf.dtype).itemsize
        return total

    @property
    def weight_quant_path(self) -> str:
        """Which matmul body the quantized programs trace: "int8:kernel"
        (fused Pallas dequant-matmul) or "int8:reference" (XLA
        dequant-then-dot — also the tp>1 path, where GSPMD partitions
        the reference natively). "none" when quantization is off.
        Mirrors kernel_path for /healthz and the bench contract."""
        if self.weight_quant == "none":
            return "none"
        quantized = [
            w for w in jax.tree_util.tree_leaves(
                self.params,
                is_leaf=lambda x: isinstance(x, QuantizedWeight),
            )
            if isinstance(w, QuantizedWeight)
        ]
        kind = (
            "kernel"
            if all(
                use_quant_matmul_kernel(self.mesh_tp, w)
                for w in quantized or [None]
            )
            else "reference"
        )
        return f"{self.weight_quant}:{kind}"

    def weight_quant_stats(self) -> Dict[str, float]:
        """Weight-quantization exposition (scheduler pump → metrics →
        gateway): mode flag, per-chip weight bytes, leaf counts."""
        return {
            "weight_quant_int8": (
                0.0 if self.weight_quant == "none" else 1.0
            ),
            "weight_bytes_device": float(self.weight_bytes_device()),
            "weight_quant_leaves": float(self._wq_stats["leaves"]),
            "weight_quant_skipped": float(self._wq_stats["skipped"]),
        }

    # -- mesh placement ----------------------------------------------------

    def _shard_params(self, params):
        """Lay the served weights out under the serving mesh: QKV
        projections split on their head columns, everything else
        replicated (_SERVING_PARAM_RULES). Identity without a mesh."""
        if self.mesh is None:
            return params
        return shard_tree(
            params, self.mesh, _serving_param_shardings()
        )

    def _shard_bank(self, bank, specs=None):
        """Place a KV bank (dense slot bank, paged page pool, or the
        exact prefix pool — dicts of [L, rows, cells, KV, hd] arrays;
        int8 scales ride along with hd==1) with the KV head axis
        sharded and every host-planned axis replicated. `specs` (a
        name -> PartitionSpec dict) overrides the per-array placement
        — the stacked adapter bank's column split rides through here
        so device_put stays inside ELASTIC-001's designated helpers.
        Identity without a mesh."""
        if self.mesh is None or bank is None:
            return bank
        if specs is None:
            sharding = named(self.mesh, serving_kv_spec())
            return {
                name: jax.device_put(arr, sharding)
                for name, arr in bank.items()
            }
        return {
            name: jax.device_put(arr, named(self.mesh, specs[name]))
            for name, arr in bank.items()
        }

    def _adapter_bank_place(self, bank):
        """DeviceAdapterCache placement callback: B banks of the
        sharded projections split their output columns on "tp" like
        the base weights (so the per-row delta lands on already-local
        columns — zero extra collectives); A banks, the wo pair and
        the scale vector replicate. Identity without a mesh."""
        if self.mesh is None:
            return bank
        return self._shard_bank(
            bank, specs=serving_adapter_specs(self.mesh)
        )

    def _replicate(self, x):
        """Replicated placement for host-planned device state (slot
        vectors, page tables): every shard addresses the full array,
        so the PR-5 async scatters and PR-6 host PageAllocator stay
        layout-oblivious. Identity without a mesh."""
        if self.mesh is None:
            return x
        return jax.device_put(x, replicated(self.mesh))

    @property
    def mesh_shape(self) -> Dict[str, int]:
        """The replica's mesh slice shape (heartbeat payload)."""
        return {"tp": self.mesh_tp}

    @property
    def n_chips(self) -> int:
        """Devices this replica occupies — the auto-scaler's unit."""
        return self.mesh_tp

    def _device_state(self) -> Dict[str, Any]:
        """Upload the host mirrors once; from here on the device
        copies advance through the chunk/spec programs and the
        scatter programs — never by per-dispatch re-upload."""
        state = {
            "tok": self._replicate(jnp.asarray(self.tok)),
            "pos": self._replicate(jnp.asarray(self.pos)),
            "done": self._replicate(jnp.asarray(self.done)),
            "limit": self._replicate(jnp.asarray(self.limit)),
            "keys": self._replicate(jnp.asarray(self.slot_key)),
        }
        if self._block:
            state["blk"] = self._replicate(jnp.asarray(self.blk))
            state["msk"] = self._replicate(jnp.asarray(self.msk))
            # the finished block a slot's next forward carries (-1:
            # none); it lives on the device alone
            state["prev"] = self._replicate(
                jnp.asarray(np.full_like(self.blk, -1))
            )
        if self._adapter_cache is not None:
            # joins the resident state ONLY when adapters are on: the
            # adapterless _dev keeps its exact pre-adapter structure
            state["adapt"] = self._replicate(jnp.asarray(self.adapt))
        if self._prefill_chunk > 0:
            # partial write frontier, same gating discipline: the
            # blocking engine's _dev keeps its exact pre-PR structure
            state["frontier"] = self._replicate(
                jnp.asarray(self._frontier)
            )
        return state

    def _next_chunk_len(self) -> int:
        """Dispatch size: `chunk` steps, shortened only when EVERY
        live slot's remaining cap (limit - pos - 1) is smaller — the
        drain tail then runs exactly to the last release instead of
        idling the whole bank.

        Measured policy note (48-req long-tail mix, 4 slots, CPU):
        chunking to the SOONEST release ("min rule") looks idle-free
        but lets every freshly admitted short request drag all slots
        to 1-2-step dispatches — dispatch overhead ate the win
        (1.05x vs lockstep). A fixed chunk with this max-cap tail
        clamp measured best (1.23x toy-scale WITH the pow2 tail
        quantization below — measured on the shipped policy;
        overheads shrink ~10x against the real-model step time on
        chip). A mid-chunk release idles one slot for at most
        chunk-1 steps while the others keep working."""
        # vectorized over the host-side [B] arrays (a Python generator
        # here costs O(n_slots) interpreter work EVERY chunk)
        live = ~self.done & ~self._prefilling & ~self._parked
        if self._block and live.any():
            return self._pow2_tail(int(self._forwards_left()[live].max()))
        if not live.any():
            # only mid-prefill slots occupied: the interleaved
            # dispatch still needs a (vacuous) decode scan — make it
            # the cheapest one (unreachable at prefill_chunk=0, where
            # _prefilling is identically False and step() gates on
            # not done.all())
            return 1
        return self._pow2_tail(int((self.limit - self.pos - 1)[live].max()))

    def _pow2_tail(self, rem: int) -> int:
        k_target = max(1, min(rem, self.chunk))
        if k_target == self.chunk:
            return k_target
        # tail values quantize DOWN to powers of two: each distinct k
        # is its own compiled scan (~tens of seconds on chip), so the
        # tail may cost log2(chunk) compiles, never chunk of them
        k = 1
        while k * 2 <= k_target:
            k *= 2
        return k

    def _forwards_left(self) -> np.ndarray:
        """[B]: the forwards each slot's request still takes, where
        generation is by diffusion over blocks: its block's denoising
        forwards, then every further block's (a finished block's keys
        and values ride with the next block's first forward, and
        nothing reads the last block's)."""
        block = self._block
        count = -(-block // self._denoise_steps)
        further = -(-(self.limit - self.pos) // block) - 1
        return (
            -(-self.msk.sum(axis=1) // count)
            + further * -(-block // count)
        )

    @property
    def weight_version(self) -> int:
        """Monotonic version of the served weights. Joins every
        program-cache key; requests/tickets record the version their
        tokens were produced under."""
        return self._weight_version

    def update_params(self, params, mode: Optional[str] = None) -> None:
        """Swap the served weights (a PPO update / a promoted
        checkpoint), version-tagged. `mode` (default: the engine's
        `weight_refresh_mode` knob) decides what happens when work is
        in flight:

        - "defer": stage the new tree and commit at the next idle
          boundary — every in-flight request completes under the
          version it started on (the fence). An idle engine commits
          immediately. This replaces the old behavior, which silently
          mixed policies mid-drain.
        - "raise": refuse a mid-drain swap with RuntimeError — for
          callers that wanted the call-between-drains contract
          enforced, not worked around.
        - "live": drain-free swap at the next dispatch boundary: any
          in-flight dispatch is abandoned (drain_inflight — replay
          regenerates its tokens), the version bumps, the
          program-cache keys retire the prior version's entries, and
          with `weight_refresh_replay` every live slot is preempted
          and replayed under the new version on its journaled key
          stream — otherwise live requests keep their old-version KV
          and finish under the new weights. Either way a single
          dispatch carries exactly one version: no mixed-version
          step exists.

        A poisoned refresh (tree structure / shape / dtype mismatch)
        raises with the prior params and version still serving, and
        counts as rolled_back in the elastic stats."""
        mode = mode or self.weight_refresh_mode
        if mode not in ("live", "defer", "raise"):
            raise ValueError(
                f"update_params mode must be 'live', 'defer' or "
                f"'raise', got {mode!r}"
            )
        busy = self.has_work()
        if mode == "raise" and busy:
            raise RuntimeError(
                "update_params while requests are in flight would mix "
                "policies mid-drain; finish the drain, or refresh "
                "with mode='defer' (fence) or mode='live' (versioned "
                "swap)"
            )
        if mode == "defer" and busy:
            try:
                self._check_refresh_tree(params)
            except Exception:
                self._elastic_refresh["rolled_back"] += 1
                raise
            self._staged_params = params
            self._elastic_refresh["deferred"] += 1
            return
        self._commit_refresh(
            params,
            replay=(
                mode == "live" and busy and self.weight_refresh_replay
            ),
        )

    def _check_refresh_tree(self, params) -> None:
        """A poisoned refresh must fail BEFORE any engine state
        changes: same tree structure, same leaf shapes and dtypes as
        the tree the engine was CONSTRUCTED with. Refresh trees arrive
        dense — they validate against the pre-quantization skeleton
        (with weight_quant="none" that skeleton IS the served tree's
        shape signature), then quantize behind the fence at commit."""
        old_leaves, old_def = jax.tree_util.tree_flatten(
            self._refresh_skeleton
        )
        new_leaves, new_def = jax.tree_util.tree_flatten(params)
        if old_def != new_def:
            raise ValueError(
                "weight refresh rejected: parameter tree structure "
                "does not match the served params"
            )
        for o, n in zip(old_leaves, new_leaves):
            o_shape = tuple(getattr(o, "shape", ()))
            n_shape = tuple(getattr(n, "shape", ()))
            if o_shape != n_shape or (
                getattr(o, "dtype", None) != getattr(n, "dtype", None)
            ):
                raise ValueError(
                    f"weight refresh rejected: leaf mismatch "
                    f"{n_shape}/{getattr(n, 'dtype', None)} vs served "
                    f"{o_shape}/{getattr(o, 'dtype', None)}"
                )

    def _commit_refresh(self, params, replay: bool = False) -> None:
        """Apply a refresh now: validate, abandon any in-flight
        dispatch, reshard, bump the version, rebind programs (the
        version joins every cache key) and retire the old version's
        cache entries. Any failure rolls back to the prior
        params/version — the engine keeps serving."""
        old_params = self.params
        old_version = self._weight_version
        old_keys = list(self._bound_keys)
        try:
            self._check_refresh_tree(params)
            self.drain_inflight()
            # quantize behind the fence: the incoming dense tree
            # re-quantizes here, and a rollback below restores the OLD
            # quantized banks — no mixed-precision tree ever serves
            self.params = self._shard_params(
                self._quantize_params(params)
            )
            self._weight_version = old_version + 1
            self._bind_programs()
        except Exception:
            self.params = old_params
            self._weight_version = old_version
            self._bind_programs()
            self._elastic_refresh["rolled_back"] += 1
            raise
        for cache, key in old_keys:
            cache.pop(key, None)  # retire stale-version closures
        if replay:
            # reverse order: _preempt_slot appendlefts, so the queue
            # front comes out in ascending slot order for replay
            for slot in range(self.n_slots - 1, -1, -1):
                req = self.slot_req[slot]
                if req is not None and not self.done[slot]:
                    self._preempt_slot(slot)
                    self._elastic_replayed += 1
        self._staged_params = None
        self._elastic_refresh["committed"] += 1

    def _maybe_commit_refresh(self) -> None:
        """Apply a deferred weight refresh once the engine is idle —
        the fence boundary: nothing live, queued or in flight, so no
        request ever spans the swap. Checked at submit() and step()."""
        if self._staged_params is not None and not self.has_work():
            self._commit_refresh(self._staged_params)

    # -- elastic resize ----------------------------------------------------

    def device_health(self) -> Dict[str, int]:
        """Live device-set health for this replica's slice. On the
        chaos-wired CPU host the deficit comes from the injector's
        lose_chip plans; a real-TPU runtime probe slots in here
        without changing any caller (pool probation, scheduler
        resize, serve_bench)."""
        lost = 0
        if self.chaos is not None:
            lost = int(self.chaos.chips_lost(self.chaos_tag))
        total = int(self._full_tp)
        return {
            "chips_total": total,
            "chips_lost": min(lost, total),
            "chips_up": max(total - lost, 0),
        }

    def surviving_chips(self) -> int:
        return self.device_health()["chips_up"]

    def resize(self, n_chips: Optional[int] = None):
        """Re-form this replica's mesh live at the largest valid tp
        <= `n_chips` surviving devices (default: what device_health
        reports). In-flight requests are preempted to the engine
        queue and replayed byte-identically at the new tp. Delegates
        the choreography to serving/elastic.py — the ONE resharding
        site outside construction (graftlint ELASTIC-001)."""
        from dlrover_tpu.serving import elastic as elastic_mod

        _refuse_unserved(self.cfg, resize=True)
        if n_chips is None:
            n_chips = self.surviving_chips()
        return elastic_mod.resize(self, n_chips)

    def elastic_stats(self) -> Dict[str, float]:
        """Elastic counters for metrics exposition (the scheduler
        copies these into ServingMetrics after each pump)."""
        return {
            "resize_shrink": float(self._elastic_resize["shrink"]),
            "resize_grow": float(self._elastic_resize["grow"]),
            "refresh_committed": float(
                self._elastic_refresh["committed"]
            ),
            "refresh_deferred": float(
                self._elastic_refresh["deferred"]
            ),
            "refresh_rolled_back": float(
                self._elastic_refresh["rolled_back"]
            ),
            "resize_downtime_ms": float(self._elastic_downtime_ms),
            "replayed_requests": float(self._elastic_replayed),
            "weight_version": float(self._weight_version),
            "tp": float(self.mesh_tp),
            "full_tp": float(self._full_tp),
        }

    # -- admission ---------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new: Optional[int] = None,
        prng_key: Optional[np.ndarray] = None,
        adapter_id: Optional[str] = None,
    ) -> int:
        """Queue one request; returns its index in the output list.
        `max_new` caps THIS request's generation (vLLM-style
        per-request max_tokens); default is the engine's. `prng_key`
        pins the request's sampling key (a failover re-admission
        continues the journaled key stream); omitted, the engine
        draws one from its seed at admission."""
        # a deferred weight refresh commits BEFORE the request enters
        # the queue: it starts (and fences) on the new version
        self._maybe_commit_refresh()
        arr = np.asarray(prompt, np.int32)
        if arr.ndim != 1 or arr.size == 0:
            raise ValueError("prompt must be a non-empty 1-D sequence")
        if max_new is not None and max_new < 1:
            raise ValueError(
                f"max_new must be >= 1, got {max_new} (omit it for "
                "the engine default)"
            )
        if arr.size + 1 > self.max_len:
            raise ValueError(
                f"prompt length {arr.size} leaves no room to generate "
                f"(max_len {self.max_len})"
            )
        if adapter_id is not None and self._adapter_cache is None:
            raise ValueError(
                "adapter_id requires an engine constructed with "
                "adapter_registry=... (multi-adapter serving is off)"
            )
        aslot = 0
        if self._adapter_cache is not None and adapter_id is not None:
            # resolve + PIN the device slot for the request's whole
            # ledger life (released at retire/cancel; preemption keeps
            # it — a replay must land on the same bank index). Raises
            # KeyError for an unregistered id and AdapterCacheFull
            # when every slot is pinned, both BEFORE the request
            # enters the ledger, so a refused submit leaks nothing.
            aslot = self._adapter_cache.acquire(adapter_id)
        req = _Request(
            idx=self._next_idx, prompt=arr, max_new=max_new or 0,
            prng_key=(
                None
                if prng_key is None
                else np.asarray(prng_key, np.uint32).reshape(2)
            ),
            adapter_id=adapter_id, adapter_slot=aslot,
        )
        self._next_idx += 1
        self._requests[req.idx] = req
        self._pending[req.idx] = None
        self._queue.append(req)
        return req.idx

    def submit_adopted(self, pkg) -> int:
        """Queue a request whose prompt KV was already prefilled on
        another replica (a serving/handoff.py KVHandoff package).
        Admission installs the shipped cells instead of running the
        prefill; everything downstream (stepping, sampling under the
        journaled key, retire) is the plain path, which is what makes
        the colocated run the byte-parity oracle."""
        idx = self.submit(
            pkg.prompt, max_new=pkg.max_new, prng_key=pkg.prng_key
        )
        self._requests[idx].adopted = pkg
        return idx

    @property
    def key(self) -> np.ndarray:
        """The engine's own key, uint32[2] on the host: `_admit` splits
        a request's key off it there, so that no admission waits for the
        device. Setting it (the PPO rollout re-keys its engine before
        every drain) is the one fetch, outside the step loop."""
        return self._key

    @key.setter
    def key(self, value) -> None:
        (self._key,) = _to_host(value)

    def _pad_to(self, toks: np.ndarray, bucket: int) -> np.ndarray:
        padded = np.full(bucket, self.pad_id, np.int32)
        padded[: len(toks)] = toks
        return padded

    def _admit(self, slot: int, req: _Request):
        p = len(req.prompt)
        # the stall this admission charges the step loop: everything
        # below until the state scatters runs synchronously — with
        # prefill_chunk>0 it shrinks to host bookkeeping because the
        # prefill itself moves into the interleaved dispatches
        bucket = self._prompt_bucket(p)
        with trace.span("engine.admit", prompt_tokens=p, bucket=bucket) as sp:
            if _dropless(self.cfg):
                # the rows a layer's grouped kernels are handed for
                # this bucket (static: nothing is fetched for it);
                # over bucket * top_k, the padding an admission carries
                sp.set(moe_rows=dropless_rows(
                    bucket * self.cfg.moe_top_k, self.cfg.held[1]))
            pf_start: Optional[int] = None
            if req.adopted is not None:
                # cross-replica handoff: install the shipped KV run and
                # skip the prefill entirely. Cleared immediately — a later
                # preemption of this slot replays from the prompt like any
                # other request (the package is single-use by design).
                from dlrover_tpu.serving import handoff as _handoff

                pkg, req.adopted = req.adopted, None
                _handoff.adopt_into_slot(self, slot, pkg)
            elif self._prefill_chunk > 0:
                # interleaved chunked admission: install the slot with a
                # partial write frontier and NO prompt forward — the step
                # loop streams the prefill in chunks fused with decode.
                # The preempted flag clears only AFTER the allocation
                # lands: a readmission that raises OutOfPages goes back
                # to the queue still marked, so it keeps waiting instead
                # of regaining preemption rights (see _admit_chunked_paged
                # on why that would livelock)
                pf_start = self._admit_chunked(slot, req, p)
                if self._paged and req.preempted:
                    req.preempted = False
                    self._swap_resumes += 1
            elif self._paged:
                if req.preempted:
                    req.preempted = False
                    self._swap_resumes += 1
                self._admit_paged(slot, req, p)
                if self._hybrid:
                    sp.set(window_cells=min(p, self.cfg.sliding_window))
            elif req.adapter_id is not None:
                # adaptered admission: the prompt K/V must come from the
                # ADAPTED projections, and it never installs from (or
                # publishes into) the shared prefix pool — published
                # prefixes are base-model K/V by contract
                self.cache = self._admit_fn(
                    self.cache,
                    self.params,
                    jnp.asarray(self._pad_to(req.prompt, bucket)),
                    slot,
                    abank=self._adapter_cache.bank,
                    aslot=req.adapter_slot,
                )
            elif self.prefix_cache is None:
                self.cache = self._admit_fn(
                    self.cache,
                    self.params,
                    jnp.asarray(self._pad_to(req.prompt, bucket)),
                    slot,
                )
            else:
                self._admit_with_prefix(slot, req, p)
            if self._block:
                self._admit_block_state(slot, req, p)
            else:
                # carry = last REAL prompt token at its position: the first
                # chunk step recomputes its logits (identical K/V rewrite)
                # and samples the first new token from them
                self.tok[slot] = req.prompt[-1]
                self.pos[slot] = p - 1
            self.limit[slot] = min(
                p + (req.max_new or self.max_new), self.max_len
            )
            if req.prng_key is None:
                # on the host: a split on the device is fetched behind
                # this admission's own prefill (DEVIATIONS §9)
                self._key, req.prng_key = host_prng.split(self._key)
            self.slot_key[slot] = req.prng_key
            self.done[slot] = False
            # mirror the admission onto the device copies as one scatter
            # (a failover re-admission's journaled key rides in key_v —
            # the resume re-key is this same program, not a re-upload)
            d = self._dev
            if self._block:
                (d["blk"], d["msk"], d["prev"], d["pos"], d["done"],
                 d["limit"]) = _state_admit_block_prog(
                    d["blk"], d["msk"], d["prev"], d["pos"], d["done"],
                    d["limit"], slot, self.blk[slot], self.msk[slot],
                    int(self.pos[slot]), int(self.limit[slot]),
                )
            else:
                d["tok"], d["pos"], d["done"], d["limit"], d["keys"] = (
                    _state_admit_prog(
                        d["tok"], d["pos"], d["done"], d["limit"],
                        d["keys"], slot, int(self.tok[slot]), p - 1,
                        int(self.limit[slot]), self.slot_key[slot],
                    )
                )
            if self._adapter_cache is not None:
                self.adapt[slot] = req.adapter_slot
                d["adapt"] = _state_adapt_prog(
                    d["adapt"], slot, int(req.adapter_slot)
                )
            if pf_start is not None:
                # mid-prefill lifecycle state: the slot is occupied (host
                # done=False, mirrors installed above) but FROZEN on
                # device (done=True — the decode scans it rides through
                # must not advance it) until the frontier reaches the
                # prompt end and _flip_to_decode re-arms it
                self._prefilling[slot] = True
                self._frontier[slot] = pf_start
                d["done"] = _state_cancel_prog(d["done"], slot)
            if self._prefill_chunk > 0:
                d["frontier"] = _state_frontier_prog(
                    d["frontier"], slot,
                    pf_start if pf_start is not None else p,
                )
        self._admission_stall_ms += sp.dur_s * 1e3
        self._admit_this_step += sp.dur_s
        self.slot_req[slot] = req
        if self.spec is not None:
            self.spec.begin_slot(slot, req.prompt)
        if self.replica_role == "prefill" and pf_start is None:
            # admission already wrote KV cells 0..p-1: the prefill is
            # DONE. Park the request for export — step() never
            # dispatches decode work on this role. (A chunked
            # admission parks in _flip_to_decode instead, once the
            # frontier actually reaches the prompt end.)
            self._prefill_ready.append(req)
            if self._prefill_chunk > 0:
                # interleaved dispatches DO run on this role while
                # other slots stream their prefills — freeze the
                # parked slot so the decode half cannot advance it
                self._parked[slot] = True
                d["done"] = _state_cancel_prog(d["done"], slot)

    def _admit_block_state(self, slot: int, req: _Request, p: int):
        """The first block of a request served by diffusion over
        blocks. Block boundaries are absolute (`pos // block`): the
        prompt's last p % block tokens open the block already
        unmasked, the rest holds the mask id; the prompt before it is
        what the admission prefilled under the block mask (the
        prefill's cells past it are dead: no query sees past its own
        block's end, and the block's forwards rewrite them). A replay
        after preemption folds whole blocks into the prompt, so it
        lands on the same boundaries."""
        given = p % self._block
        self.pos[slot] = p - given
        self.blk[slot] = self.cfg.mask_token_id
        self.blk[slot, :given] = req.prompt[p - given:]
        self.msk[slot] = np.arange(self._block) >= given

    def _admit_with_prefix(self, slot: int, req: _Request, p: int):
        """Prefix-cached admission: install the longest cached
        block-aligned prefix, prefill only the suffix bucket, publish
        the request's own aligned prefix for the next arrival."""
        pc = self.prefix_cache
        if self.kv_tier is not None:
            self._tier_promote_prefix(req)
        matched, row = pc.match(req.prompt)
        # a matched depth whose suffix bucket would overrun max_len
        # retreats block by block (the pool row stays valid for any
        # shallower start); start==0 degrades to a cold admission
        start = min(matched, p)
        while start > 0 and start + _pad_bucket(p - start) > self.max_len:
            start -= pc.block
        start = max(start, 0)
        work = None
        if start <= 0 or row is None:
            bucket = self._prompt_bucket(p)
            self.cache, work = self._admit_cold_fn(
                self.cache,
                self.params,
                jnp.asarray(self._pad_to(req.prompt, bucket)),
                slot,
            )
            pc.record_admission(0)
        else:
            # pin the row for the life of the slot occupancy: install
            # copies the K/V, but the pin is the invariant ("never
            # evict under a live slot") a zero-copy backend will need
            pc.acquire(row)
            self._slot_row[slot] = row
            if start >= p:
                self.cache = self._admit_hit_fn(
                    self.cache, self.pool, slot, row
                )
            else:
                suffix = self._pad_to(
                    req.prompt[start:], _pad_bucket(p - start)
                )
                self.cache, work = self._admit_warm_fn(
                    self.cache,
                    self.pool,
                    self.params,
                    jnp.asarray(suffix),
                    slot,
                    row,
                    start,
                )
            pc.record_admission(start)
        # publish the aligned prefix when it is deeper than what was
        # cached (at admission time, not retire: the K/V is fresh in
        # the working row, and the NEXT request in this very batch —
        # the shared-system-prompt case — already hits)
        publish_len = pc.aligned_len(p)
        if work is not None and publish_len > matched:
            new_row, is_new = pc.insert(req.prompt[:publish_len])
            if is_new:
                self.pool = self._publish_fn(self.pool, work, new_row)

    def _admit_chunked(self, slot: int, req: _Request, p: int):
        """Chunked admission (prefill_chunk > 0): run NO prompt
        forward here — only install any cached prefix and report
        where the interleaved dispatcher must start prefilling.

        Returns the initial frontier (0 for a cold prompt, the
        matched depth for a warm one), or None when nothing is owed
        (a full prefix hit — the slot then admits live, exactly like
        the blocking path's hit branch). Chunked admissions never
        publish into the prefix cache: publishing needs the exact
        fp32 work row the blocking prefill programs return, and the
        chunked path deliberately never materializes one."""
        if self._paged:
            return self._admit_chunked_paged(slot, req, p)
        pc = self.prefix_cache
        start = 0
        # adaptered requests bypass the prefix cache (published
        # prefixes are base-model K/V by contract), same as blocking
        if pc is not None and req.adapter_id is None:
            if self.kv_tier is not None:
                self._tier_promote_prefix(req)
            matched, row = pc.match(req.prompt)
            start = min(matched, p)
            if start > 0 and row is not None:
                pc.acquire(row)
                self._slot_row[slot] = row
                # the hit program copies the WHOLE cached row; cells
                # beyond the matched depth hold the publisher's
                # garbage, which is dead — every chunk writes cell j
                # before any later query attends j
                self.cache = self._admit_hit_fn(
                    self.cache, self.pool, slot, row
                )
                pc.record_admission(start)
                if start >= p:
                    return None
            else:
                start = 0
                pc.record_admission(0)
        return start

    def _admit_chunked_paged(self, slot: int, req: _Request, p: int):
        """Paged twin of _admit_chunked: allocate the slot's FULL
        page run up front (every chunk position must map to an owned
        page before the fused program writes it), share any matched
        prefix's leading pages copy-free, and report the frontier.
        No retreat loop: chunks are exact-length slices of the real
        prompt, so there is no pad bucket to overrun max_len.

        Swap rights are seniority-gated: only a NEVER-preempted
        arrival may reclaim by preempting a live slot. Blocking
        admission completes the whole prefill inside _admit, so every
        swap round nets forward progress; a chunked admission only
        installs a frontier, and two requests that each fit alone but
        not together would otherwise evict each other's zero-token
        frontiers forever (admit A, preempt mid-prefill B, readmit B,
        preempt mid-prefill A, ...). Every preemption strips the
        victim's swap rights, so mutual-eviction cycles cannot form:
        a preempted readmission that cannot alloc waits in the queue
        (step() requeues it) until a live slot retires."""
        pc = self.prefix_cache
        lora = req.adapter_id is not None
        if self.kv_tier is not None and self._tier_swap_in(
            slot, req, p
        ):
            # full swap-in: the run is resident, the frontier page is
            # exclusively owned — the slot admits live (the blocking
            # path's full-hit semantics)
            return None
        n_need = self._request_pages(req)
        matched, row, start = 0, None, 0
        if pc is not None and not lora:
            if self.kv_tier is not None:
                self._tier_promote_prefix(req)
            matched, row = pc.match(req.prompt)
            start = min(matched, p)
            if row is None or row not in self._row_pages:
                start = 0
        shared: List[int] = []
        if start > 0:
            pc.acquire(row)
            self._slot_row[slot] = row
            shared = self._row_pages[row][: start // self.page_size]
            self.allocator.share(shared)
        try:
            own = self._alloc_pages(
                n_need - len(shared), swap_ok=not req.preempted
            )
        except OutOfPages:
            if shared:
                self.allocator.free(shared)
                self._release_slot_row(slot)
            raise
        run = shared + own
        self._slot_pages[slot] = run
        full_hit = pc is not None and start >= p and start > 0
        if full_hit:
            # decode's first step rewrites cell p-1, which sits in a
            # shared page: CoW before the table row is built so vals
            # picks up the fresh page (mutates run in place)
            self._cow_frontier(slot, p)
        vals = np.full(self._pages_per_slot, TRASH_PAGE, np.int32)
        vals[: len(run)] = run
        self._table = _table_row_prog(self._table, slot, vals)
        if pc is not None and not lora:
            pc.record_admission(start)
        if full_hit:
            return None
        # start is block-aligned and block % page_size == 0, so the
        # first chunk write lands in an OWN page — shared pages are
        # never written, no warm-path CoW needed
        return start

    def _release_slot_row(self, slot: int):
        row = self._slot_row[slot]
        if row is not None:
            self.prefix_cache.release(row)
            self._slot_row[slot] = None

    # -- paged admission (kv_layout="paged") -------------------------------

    def _on_prefix_evict(self, row: int, blocks=()) -> None:
        """Radix eviction callback: the published prefix's page run
        drops its reference — pages nobody else holds return to the
        free list (no device work; the bytes just become dead). With
        a host tier, eviction becomes DEMOTION first: the row's exact
        bytes are gathered and their async D2H copy started before
        the run is released, so the prefix survives one rung down."""
        run = self._row_pages.pop(row, None) if self._paged else None
        if self.kv_tier is not None and blocks:
            self._tier_demote_row(row, blocks)
        if run:
            self.allocator.free(run)

    # -- host-DRAM KV tier (serving/kv_tier.py) ----------------------------

    def _tier_demote_row(self, row: int, blocks) -> None:
        """Demote an evicted published prefix: gather its exact pool
        row (static-width bucket) and hand the in-flight staging
        buffers to the tier. Never raises into the eviction path — a
        failed demotion (tier full, chaos fault mid-demotion) just
        means the prefix dies the way it always did, and readmission
        falls back to a cold prefill."""
        tokens = [t for blk in blocks for t in blk]
        depth = len(tokens)
        if depth <= 0 or self.pool is None:
            return
        w = min(_pad_bucket(depth), self.max_len)
        try:
            staged = _kv_tier.snapshot_row(self.pool, row, w)
            self.kv_tier.put_prefix(tokens, staged, depth)
        # graftlint: allow(EXC-001) reason=demotion is an opportunistic save; the eviction it rides must complete regardless, and replay/cold-prefill remains correct
        except Exception:  # noqa: BLE001
            self.kv_tier.note_demote_failure()

    def _tier_alloc(self, n: int, swap_ok: bool = True):
        """_alloc_pages' promotion twin: the same reclaim loop, but
        pages come out of allocator.promote() so PCIe-paid installs
        stay observable next to cold allocs and handoff adoptions."""
        while True:
            try:
                return self.allocator.promote(n)
            except OutOfPages:
                if not self._reclaim_pages(swap_ok):
                    raise

    def _tier_promote_prefix(self, req: _Request) -> None:
        """Pre-admission promotion check: if the host tier holds a
        strictly deeper prefix of this prompt than the radix cache,
        upload it into a fresh pool row (and, paged, install it into
        promoted pages) and re-publish — the admission match that
        follows then hits it through the EXISTING warm/full-hit
        paths, so promoted bytes flow through the same install
        programs as originally published ones (byte parity for
        free)."""
        tier, pc = self.kv_tier, self.prefix_cache
        if tier is None or pc is None or self._tier_promote != "always":
            return
        matched, _ = pc.match(req.prompt)
        ent = tier.match_prefix(req.prompt, min_depth=matched)
        if ent is None:
            return
        tier.acquire(ent)
        try:
            pages = None
            if self._paged:
                n_pg = ent.depth // self.page_size
                try:
                    pages = self._tier_alloc(
                        n_pg, swap_ok=not req.preempted
                    )
                except OutOfPages:
                    return  # pool dry: admission proceeds cold
            row, is_new = pc.insert(list(ent.tokens))
            if row is None or not is_new:
                # every row pinned, or a racing publish beat us —
                # nothing to upload; return the pages untouched
                if pages:
                    self.allocator.free(pages)
                return
            self.pool, dev_row = _kv_tier.upload_row(
                self.pool, ent, row
            )
            if pages is not None:
                vals = np.full(
                    self._pages_per_slot, TRASH_PAGE, np.int32
                )
                vals[: len(pages)] = pages
                w = next(iter(ent.data.values())).shape[2]
                self.page_pool = _kv_tier.install_row_pages(
                    self.page_pool, dev_row, vals, w
                )
                self._row_pages[row] = pages
            tier.note_promoted(ent)
        finally:
            tier.release(ent)

    def _tier_swap_in(self, slot: int, req: _Request, p: int) -> bool:
        """Swap-to-host readmission: if the tier holds this exact
        folded sequence's page run, promote fresh pages, scatter the
        stored bytes onto them, and point the slot's table at the
        result — the prefill is skipped entirely and the admission
        tail resumes from the journaled position/key. False → the
        caller runs the normal (replay) admission."""
        tier = self.kv_tier
        if (
            tier is None
            or not self._tier_swap
            or self._tier_promote == "never"
        ):
            return False
        salt = req.adapter_id or ""
        ent = tier.peek_swap(req.prompt, salt=salt)
        if ent is None:
            return False
        n_need = self._request_pages(req)
        if ent.n_pages > n_need or ent.page_size != self.page_size:
            return False
        tier.acquire(ent)
        try:
            try:
                pages = self._tier_alloc(
                    n_need, swap_ok=not req.preempted
                )
            except OutOfPages:
                return False  # replay path may still fit via sharing
            self._slot_pages[slot] = pages
            self.page_pool = _kv_tier.upload_pages(
                self.page_pool, ent, pages[: ent.n_pages]
            )
            vals = np.full(self._pages_per_slot, TRASH_PAGE, np.int32)
            vals[: len(pages)] = pages
            self._table = _table_row_prog(self._table, slot, vals)
        finally:
            tier.release(ent)
        tier.consume(ent)
        return True

    def _tier_swap_out_slot(self, slot: int, tokens) -> None:
        """Swap-to-host demotion of a preempted victim: snapshot the
        pages covering its valid cells [0, len(tokens)) and start
        their D2H copies before the run is freed. Only a cleanly
        decoding slot qualifies (mid-prefill KV is partial — replay
        is already the cheap path there); any failure just leaves
        replay as the fallback."""
        tier = self.kv_tier
        if (
            tier is None
            or not self._tier_swap
            or not self._paged
            or self._prefilling[slot]
            or self._parked[slot]
        ):
            return
        p = len(tokens)
        if p <= 0 or int(self.pos[slot]) + 1 != p:
            return
        run = self._slot_pages[slot]
        n_keep = (p - 1) // self.page_size + 1
        if n_keep > len(run):
            return
        req = self.slot_req[slot]
        salt = (req.adapter_id or "") if req is not None else ""
        try:
            staged = _kv_tier.snapshot_pages(
                self.page_pool, run[:n_keep]
            )
            tier.put_swap(
                tokens, staged, n_keep, self.page_size, salt=salt
            )
        # graftlint: allow(EXC-001) reason=demotion is an opportunistic save; the preemption it rides must complete regardless, and resume-by-replay remains correct
        except Exception:  # noqa: BLE001
            tier.note_demote_failure()

    def swap_out(self, idx: int) -> None:
        """cancel() with demotion: the scheduler's admission
        preemption calls this instead of cancel so the victim's live
        page run swaps to host — readmission then promotes it back
        and resumes over PCIe instead of replaying the whole prefill.
        Exactly cancel() when the tier is off or the slot does not
        qualify."""
        req = self._requests.get(idx)
        if (
            req is not None
            and self.kv_tier is not None
            and self._tier_swap
            and self._paged
        ):
            for slot in range(self.n_slots):
                if self.slot_req[slot] is req and not self.done[slot]:
                    tokens = list(req.prompt) + [
                        int(t) for t in req.out[req.folded:]
                    ]
                    self._tier_swap_out_slot(slot, tokens)
                    break
        self.cancel(idx)

    def kv_tier_stats(self) -> Dict[str, float]:
        """Host-tier telemetry for ServingMetrics / the gateway:
        bytes, entries, demotion/promotion/swap/eviction counters and
        the promote hit rate. {} when the tier is off."""
        if self.kv_tier is None:
            return {}
        return self.kv_tier.stats()

    def health_stats(self) -> Dict[str, float]:
        """KV-integrity telemetry (serving/health.py) for
        ServingMetrics / the gateway: verifications and quarantines
        across every checksum site this engine owns (tier ingress +
        handoff adopt). {} with the knob off and nothing ever
        verified, so the legacy telemetry stream is unchanged."""
        checks = float(self._integrity_checks)
        quarantines = float(self._integrity_quarantines)
        if self.kv_tier is not None:
            ts = self.kv_tier.stats()
            checks += ts["integrity_checks"]
            quarantines += ts["quarantines"]
        if not self.kv_checksums and checks == 0 and quarantines == 0:
            return {}
        return {
            "kv_checksums": float(self.kv_checksums),
            "integrity_checks": checks,
            "integrity_quarantines": quarantines,
        }

    def _request_pages(self, req: _Request) -> int:
        """Exact page need for a request: its OWN limit (prompt plus
        its token budget, capped at max_len), not max_len — short
        requests stop stranding the tail of a dense row. The highest
        cell ever written is limit-1+K (a frozen done slot rewrites
        its last cell; a verify window extends K past it)."""
        p = len(req.prompt)
        limit = min(p + (req.max_new or self.max_new), self.max_len)
        if self._block:
            # a forward writes its whole block: the END of the block
            # that holds the request's last position
            limit = -(-limit // self._block) * self._block
        return (
            (limit - 1 + self.spec_draft_len) // self.page_size + 1
        )

    def _admit_paged(self, slot: int, req: _Request, p: int):
        """Paged admission: size the request's page run off its OWN
        limit (not max_len — short requests stop stranding the tail
        of a dense row), point the leading table entries at any
        matched prefix's pages copy-free, allocate the rest, and
        install only the cells the shared pages don't already hold.
        Pool pressure is resolved inline: evict unreferenced prefix
        runs, then preempt-and-swap the coldest live request."""
        if self._hybrid:
            self._admit_paged_hybrid(slot, req, p)
            return
        pc = self.prefix_cache
        # adaptered requests bypass the prefix cache both ways: a
        # published prefix holds base-model K/V (wrong bytes for this
        # adapter), and this adapter's K/V must never publish
        lora = req.adapter_id is not None
        if self.kv_tier is not None and self._tier_swap_in(
            slot, req, p
        ):
            # full swap-in: the resumed run is resident and owned; no
            # prefill, no prefix bookkeeping — the admission tail
            # restores carry/pos/limit/key from the journaled request
            return
        n_need = self._request_pages(req)
        matched, row, start = 0, None, 0
        if pc is not None and not lora:
            if self.kv_tier is not None:
                self._tier_promote_prefix(req)
            matched, row = pc.match(req.prompt)
            start = min(matched, p)
            while (
                start > 0
                and start + _pad_bucket(p - start) > self.max_len
            ):
                start -= pc.block
            start = max(start, 0)
            if row is None or row not in self._row_pages:
                start = 0
        shared: List[int] = []
        if start > 0:
            # pin the matched row BEFORE any reclaim can run: an
            # eviction pass must never free the run we are sharing
            pc.acquire(row)
            self._slot_row[slot] = row
            shared = self._row_pages[row][: start // self.page_size]
            self.allocator.share(shared)
        try:
            own = self._alloc_pages(n_need - len(shared))
        except OutOfPages:
            if shared:
                self.allocator.free(shared)
                self._release_slot_row(slot)
            raise
        run = shared + own
        self._slot_pages[slot] = run
        full_hit = pc is not None and start >= p and start > 0
        if full_hit:
            # the write frontier (cell p-1, rewritten by the first
            # chunk step) sits inside the last shared page: CoW it
            # now, while the copy still reads the publisher's bytes
            self._cow_frontier(slot, p)
        # numpy on purpose: the jit dispatch transfers it with the
        # call instead of an extra eager device op per admission
        vals = np.full(self._pages_per_slot, TRASH_PAGE, np.int32)
        vals[: len(run)] = run
        work = None
        if full_hit:
            # no install program at all: the table row is the only
            # device write a full-prefix hit needs
            self._table = _table_row_prog(self._table, slot, vals)
            if pc is not None:
                pc.record_admission(start)
        elif start > 0:
            suffix = self._pad_to(
                req.prompt[start:], _pad_bucket(p - start)
            )
            self.page_pool, self._table, work = self._paged_warm_fn(
                self.page_pool,
                self._table,
                self.pool,
                self.params,
                suffix,
                slot,
                vals,
                row,
                start,
            )
            pc.record_admission(start)
        elif lora:
            bucket = self._prompt_bucket(p)
            # adapted prefill; `work` stays None — the exact row this
            # program returns must never publish into the shared pool
            self.page_pool, self._table, _ = self._paged_cold_fn(
                self.page_pool,
                self._table,
                self.params,
                self._pad_to(req.prompt, bucket),
                slot,
                vals,
                abank=self._adapter_cache.bank,
                aslot=req.adapter_slot,
            )
        else:
            bucket = self._prompt_bucket(p)
            self.page_pool, self._table, work = self._paged_cold_fn(
                self.page_pool,
                self._table,
                self.params,
                self._pad_to(req.prompt, bucket),
                slot,
                vals,
            )
            if pc is not None:
                pc.record_admission(0)
        # publish AFTER install (the published pages must hold the
        # installed bytes): the run's leading pages become the radix
        # entry's run by ref-count alone — publish copies the fp32
        # work row into the prefix pool (the suffix-prefill source)
        # but never copies K/V into or out of the page pool
        if pc is not None and work is not None:
            publish_len = pc.aligned_len(p)
            if publish_len > matched:
                new_row, is_new = pc.insert(req.prompt[:publish_len])
                if is_new:
                    pub = list(run[: publish_len // self.page_size])
                    self.allocator.share(pub)
                    self._row_pages[new_row] = pub
                    self.pool = self._publish_fn(
                        self.pool, work, new_row
                    )
        # whoever now shares the frontier page (a publish of a
        # page-aligned prompt), the SLOT must own its copy before
        # decode rewrites cell p-1
        self._cow_frontier(slot, p)

    def _prompt_bucket(self, p: int) -> int:
        """The padded length a cold prompt of `p` tokens is prefilled
        at: the next power of two (`_pad_bucket`), capped at max_len.
        Above 1024 a power of two pads a 2100-token prompt by 70%, and
        a blocking prefill stalls every slot for its padding too, so
        buckets there step by 512, wherever nothing else reckons a
        prompt's run in powers of two: the prefix cache, the host tier
        and the handoff between replicas do (their stored and shipped
        rows), and with one of them the buckets stay powers of two."""
        free = (
            self.prefix_cache is None and self.kv_tier is None
            and self.replica_role == "colocated"
        )
        if free and p > 1024:
            return min(-(-p // 512) * 512, self.max_len)
        return min(_pad_bucket(p), self.max_len)

    def _admit_paged_hybrid(self, slot: int, req: _Request, p: int):
        """Admission where window and full layers mix: the full class
        gets the request's whole run (sized off its own limit, as
        ever), the window class the pages of the prompt's last
        `sliding_window` positions, and one program prefills the
        bucket and installs both. No prefix cache, tier or adapter
        reaches here (`_refuse_unserved`)."""
        run = self._alloc_pages(self._request_pages(req))
        self._slot_pages[slot] = run
        window = self.cfg.sliding_window
        self.rings.hold(slot, max(p - window, 0), p - 1)
        vals = np.full(self._pages_per_slot, TRASH_PAGE, np.int32)
        vals[: len(run)] = run
        bucket = self._prompt_bucket(p)
        self.page_pool, self._table = self._paged_cold_hybrid_fn(
            self.page_pool,
            self._table,
            self.params,
            self._pad_to(req.prompt, bucket),
            slot,
            vals,
            self.rings.table[slot].copy(),
            p,
        )

    def _hold_rings(self, k: int) -> None:
        """Between dispatches, never per token: every live slot's ring
        is moved up to the cells the next `k` steps read and write.
        The pages wholly behind the window go back to the window
        class, the pages ahead of the frontier are allocated."""
        window = self.cfg.sliding_window
        for slot in range(self.n_slots):
            if self.done[slot] or self.slot_req[slot] is None:
                continue
            pos = int(self.pos[slot])
            self._window_freed_this_step += self.rings.hold(
                slot, max(pos - window + 1, 0),
                min(pos + k - 1, int(self.limit[slot]) - 1),
            )

    def _alloc_pages(self, n: int, swap_ok: bool = True) -> List[int]:
        """Allocate with reclaim: on a dry pool, evict LRU
        unreferenced prefix runs first (free memory nobody is using),
        then preempt-and-swap live requests until the allocation
        fits. `swap_ok=False` (a preempted chunked readmission)
        stops after eviction — it may reclaim free memory but not
        evict live work, the anti-livelock gate _admit_chunked_paged
        documents. Raises OutOfPages only when nothing is left to
        reclaim."""
        while True:
            try:
                return self.allocator.alloc(n)
            except OutOfPages:
                if not self._reclaim_pages(swap_ok):
                    raise

    def _reclaim_pages(self, swap_ok: bool = True) -> bool:
        """One reclaim step. Eviction is strictly cheaper than
        preemption (no replay), so prefix runs go first."""
        pc = self.prefix_cache
        if pc is not None and pc.evict_lru():
            return True  # _on_prefix_evict freed the run
        if not swap_ok:
            return False
        slot = self._pick_preempt_slot()
        if slot is None:
            return False
        self._preempt_slot(slot)
        return True

    def _slot_progress(self, slot: int) -> int:
        """Preemption coldness of an occupied slot. Mid-decode: pos
        (resident KV cells — the replay cost). Mid-prefill: NEGATIVE
        (frontier - prompt length, the cells still owed) — a slot
        that has consumed prompt but emitted nothing is strictly
        cheaper to evict than ANY decoding slot (replay regenerates
        zero tokens), and among prefilling slots the one furthest
        from its prompt end is cheapest. Identical to the old
        pos-only ranking when prefill_chunk=0 (\\_prefilling is
        identically False)."""
        if self._prefilling[slot]:
            return int(self._frontier[slot]) - len(
                self.slot_req[slot].prompt
            )
        return int(self.pos[slot])

    def _pick_preempt_slot(self) -> Optional[int]:
        """Coldest live slot = the smallest resident KV footprint
        (fewest decoded cells; mid-prefill slots rank below every
        decoding one): cheapest to swap out and replay. Deterministic
        tie-break by slot index keeps parity sweeps reproducible."""
        best, best_prog = None, None
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None or (
                self.done[slot] and not self._prefilling[slot]
            ):
                continue
            prog = self._slot_progress(slot)
            if best_prog is None or prog < best_prog:
                best, best_prog = slot, prog
        return best

    def _preempt_slot(self, slot: int) -> None:
        """Swap a live request out to host: its device state IS
        reconstructible from host data (prompt + emitted tokens +
        current PRNG key — the PR-4 resume-by-replay contract), so
        'swap' means free the pages and re-queue a replay request at
        the front. Greedy replay is byte-identical; sampled replay
        continues the exact key stream (seed-stable, the crash-
        failover contract)."""
        req = self.slot_req[slot]
        emitted = np.asarray(req.out[req.folded :], np.int32)
        if emitted.size:
            req.prompt = np.concatenate([req.prompt, emitted])
        req.folded = len(req.out)
        # same absolute cap: replay generates exactly the tokens the
        # uninterrupted run still owed
        req.max_new = max(int(self.limit[slot]) - len(req.prompt), 1)
        req.prng_key = self.slot_key[slot].copy()
        req.preempted = True
        if self._paged:  # dense slots have no page run to free
            # swap-to-host: the victim's valid cells demote before the
            # run is freed — readmission promotes them back over PCIe
            # instead of replaying the whole prefill (replay stays the
            # fallback when the tier is off/full/faulted)
            if self.kv_tier is not None:
                self._tier_swap_out_slot(slot, req.prompt)
            self._release_slot_pages(slot)
        if self.prefix_cache is not None:
            self._release_slot_row(slot)
        # a mid-prefill victim re-queues with out=[] and its ORIGINAL
        # admission key (the mirror holds it — harvest re-asserts it
        # against scan drift): replay re-prefills from scratch,
        # byte-identical to an undisturbed admission
        self._clear_prefill(slot)
        self.slot_req[slot] = None
        self.done[slot] = True
        self._dev["done"] = _state_cancel_prog(self._dev["done"], slot)
        try:
            # a preempted prefill's KV is gone — it must re-prefill at
            # re-admission, not export a dead page run
            self._prefill_ready.remove(req)
        except ValueError:
            pass
        self._queue.appendleft(req)
        if self._paged:
            self._swap_preemptions += 1

    def _release_slot_pages(self, slot: int) -> None:
        """Drop a slot's page run — pure host accounting. No device
        dispatch: the chunk/verify programs route done rows through
        the trash page themselves (the device done flag is set before
        or by the same dispatch that finishes the slot), so the stale
        table row is harmless until admission overwrites it."""
        run = self._slot_pages[slot]
        if run:
            self.allocator.free(run)
            self._slot_pages[slot] = []
        if self._hybrid:
            self.rings.release(slot)

    def _cow_frontier(self, slot: int, p: int) -> None:
        """Ensure the slot exclusively owns the page holding its
        write frontier (cell p-1). Shared — by a full-prefix hit or
        a page-aligned publish — means one page copy: the slot gets
        a fresh page preloaded with the shared page's cells, readers
        keep the original. This is the ONLY CoW site: every cell the
        slot writes later lives in pages past every published run."""
        run = self._slot_pages[slot]
        idx = (p - 1) // self.page_size
        if idx >= len(run):
            return
        page = run[idx]
        if self.allocator.refcount(page) <= 1:
            return
        while True:
            try:
                fresh, copied = self.allocator.cow(page)
                break
            except OutOfPages:
                if not self._reclaim_pages():
                    raise
        if copied:
            self.page_pool = self._page_copy_fn(
                self.page_pool, page, fresh
            )
            run[idx] = fresh
            self._table = _table_entry_prog(
                self._table, slot, idx, fresh
            )

    def admission_headroom_ok(self) -> bool:
        """Memory-aware admission gate for the scheduler: True when a
        worst-case admission fits the free pool (plus swap_headroom
        slack) without evicting or preempting. Admission past a False
        still SUCCEEDS — the engine reclaims inline — this only lets
        the scheduler prefer queue-waiting over swap-thrash while
        other requests are draining. Dense layout: always True."""
        if not self._paged:
            return True
        # count admissions the engine has accepted but not yet stepped
        # (their pages are not allocated yet, so free_pages alone
        # would happily over-admit a whole burst in one pump). Queued
        # requests' needs are EXACT — prompt and budget are known at
        # submit — so a dense-equivalent pool still fills every slot
        # in one pump; only the unknown next request is worst-cased.
        pending = sum(self._request_pages(r) for r in self._queue)
        want = min(
            self._pages_per_slot + self.swap_headroom,
            self.allocator.capacity,
        )
        if self.allocator.free_pages < pending + want:
            return False
        if self._hybrid:
            # the window class: a ring a queued request and one more
            ring = self.rings.ring_pages
            return self.allocator_win.free_pages >= ring * (
                len(self._queue) + 1
            )
        return True

    def paged_stats(self) -> Dict[str, float]:
        """Page-pool telemetry for ServingMetrics / the gateway:
        occupancy, sharing ratio, CoW copies, preempt/swap counters.
        {} under the dense layout."""
        if not self._paged:
            return {}
        s = self.allocator.stats()
        s["swap_preemptions"] = float(self._swap_preemptions)
        s["swap_resumes"] = float(self._swap_resumes)
        if self._hybrid:
            s["window_pages_held"] = float(self.rings.pages_held)
            s["window_pages_freed"] = float(
                self.rings.pages_freed_behind
            )
        if self._moe_routed_total:
            # a share of the experts is held here: of the pairs the
            # router dealt, the share that landed on them
            s["moe_held_pairs_share"] = (
                self._moe_held_total / self._moe_routed_total
            )
        if self._diff_forwards_total:
            s["diffusion_tokens_per_forward"] = (
                self._diff_tokens_total / self._diff_forwards_total
            )
        return s

    def adapter_stats(self) -> Dict[str, float]:
        """Adapter-serving telemetry for ServingMetrics / the gateway:
        registry size, device-bank residency, hit/miss/eviction/upload
        counters, and live adaptered requests. {} when multi-adapter
        serving is off."""
        if self._adapter_cache is None:
            return {}
        s = {
            k: float(v)
            for k, v in self._adapter_cache.stats().items()
        }
        s["registered"] = float(len(self.adapter_registry))
        s["active_requests"] = float(
            sum(
                1
                for r in self._requests.values()
                if r.adapter_id is not None
            )
        )
        return s

    def prefill_stats(self) -> Dict[str, float]:
        """Interleaved chunked-prefill telemetry for ServingMetrics /
        the gateway: the knob, cumulative admission stall charged to
        the step loop, interleaved chunks dispatched, and how many
        slots are mid-prefill right now. Present (with zeros) even at
        prefill_chunk=0 so the /metrics exposition — and the TTFT
        decomposition it enables — is unconditional."""
        return {
            "prefill_chunk": float(self._prefill_chunk),
            "admission_stall_ms": self._admission_stall_ms,
            "prefill_chunks_total": float(self._prefill_chunks_total),
            "prefilling_slots": float(int(self._prefilling.sum())),
        }

    def adapter_active(self) -> Dict[str, int]:
        """Ledger-live (queued, in-slot, or finished-unretired)
        request count per adapter id — the gateway's per-adapter
        active block."""
        out: Dict[str, int] = {}
        for r in self._requests.values():
            if r.adapter_id is not None:
                out[r.adapter_id] = out.get(r.adapter_id, 0) + 1
        return out

    def adapter_residency(self) -> List[str]:
        """Adapter ids resident in the device bank (MRU last) — the
        replica heartbeat's routing hint; [] when adapters are off."""
        if self._adapter_cache is None:
            return []
        return self._adapter_cache.resident_ids()

    # -- the loop ----------------------------------------------------------

    def has_work(self) -> bool:
        """True while any slot is live, the queue holds requests, or
        a dispatch is still in flight (async mode: its events have
        not surfaced yet, so one more step() is owed)."""
        return (
            bool(self._queue)
            or not self.done.all()
            or self._inflight is not None
        )

    def queue_len(self) -> int:
        """Requests waiting for a slot (excludes live slots)."""
        return len(self._queue)

    def active_count(self) -> int:
        """Slots currently decoding."""
        return int((~self.done).sum())

    def free_slots(self) -> int:
        return self.n_slots - self.active_count()

    def drain_inflight(self) -> None:
        """Abandon any dispatched-but-unharvested step. Evacuation
        calls this before snapshotting: the journal and request
        outputs then reflect exactly the last HARVESTED dispatch (a
        consistent pair), and failover replay regenerates whatever
        the abandoned dispatch would have emitted, byte-identically,
        from the journaled per-slot keys."""
        self._inflight = None

    def step_stats(self) -> Dict[str, float]:
        """Cumulative step-latency micro-stats for metrics exposition:
        host_ms (host-side work inside step(), waits excluded),
        device_wait_ms (time blocked on device results), dispatches,
        and overlap_ratio = hidden device span / total device span —
        ~0 in sync mode, approaching 1 when the host fully hides the
        device under async dispatch. compile_s and compilations: what
        the build and the steps since spent tracing, lowering and
        compiling (or reading the cache), and the programs that took —
        level once every shape is warm."""
        ratio = (
            self._stat_overlap_ms / self._stat_span_ms
            if self._stat_span_ms > 0
            else 0.0
        )
        return {
            "host_ms": self._stat_host_ms,
            "device_wait_ms": self._stat_wait_ms,
            "dispatches": float(self._stat_dispatches),
            "overlap_ratio": ratio,
            "compile_s": self._stat_compile_s,
            "compilations": float(self._stat_compilations),
        }

    def step(self) -> List[StepEvent]:
        """One engine iteration, a dispatch kept in flight
        (`async_depth=1`, the default): harvest the PREVIOUS dispatch
        first (its host copies were started at enqueue, so the wait is
        only whatever device time the host failed to hide), admit and
        draft from that fully-refreshed state WITHOUT fetching from
        the device, enqueue the next dispatch without blocking on it,
        and return the harvested events — so the caller streams and
        journals dispatch N-1, and admits from its own queue, while
        the device computes dispatch N. `async_depth=0` admits, runs
        ONE dispatch, harvests it and returns its events in the same
        call: the oracle the parity tests build by name. Returns []
        when there is no work. Either way drafting and admission see
        the same state sequence, so the dispatches (and the emitted
        token streams) are byte-identical across depths; only WHEN
        events surface shifts by one call. The span's `overlap_s` is
        the device span of the harvested dispatch that the host did
        not spend waiting (`step_stats()["overlap_ratio"]` sums it),
        its `compile_s` what the step spent on jax's compile path: 0.0
        unless an admission or a dispatch met a shape for the first
        time (the `compile` records under it say which)."""
        compile_s, compiled = trace.compiled()
        with trace.span("engine.step") as sp:
            self._wait_this_step = 0.0
            self._overlap_this_step = 0.0
            self._admit_this_step = 0.0
            self._window_freed_this_step = 0
            self._moe_pairs = None
            self._latent_cells = 0
            self._diff = None
            self._maybe_commit_refresh()  # deferred swap at idle fence
            if self.chaos is not None:
                # before the harvest, any admission or dispatch: an
                # injected fault leaves the queue, ledger, cache AND the
                # dispatch in flight untouched, which is the state
                # between two steps. A caller that evacuates or re-forms
                # the mesh drains the dispatch itself (drain_inflight);
                # one that goes on with the same mesh (resize's no-op: the
                # chip lost was none of this slice's) harvests it next
                # step, and no token is lost
                step_no = self._step_no
                self._step_no += 1
                self.chaos.on_engine_step(self.chaos_tag, step_no)
            try:
                if self.kv_tier is not None:
                    # complete last step's demotion copies (started async
                    # at demote time — a whole dispatch has passed, so
                    # this is a completion, not a stall) and release their
                    # staging buffers
                    self.kv_tier.drain()
                events = self._harvest()
                for slot in range(self.n_slots):
                    if self.done[slot] and self._queue:
                        req = self._queue.popleft()
                        try:
                            self._admit(slot, req)
                        except OutOfPages:
                            # chunked admission only: a preempted
                            # readmission has no swap rights (the
                            # anti-livelock gate), so a dry pool means
                            # wait — requeue at the front and let the
                            # live slots drain pages. Hard exhaustion
                            # (nothing live to wait on) still raises,
                            # same as the blocking path.
                            if self._prefill_chunk == 0 or not any(
                                self.slot_req[s] is not None
                                for s in range(self.n_slots)
                            ):
                                raise
                            self._queue.appendleft(req)
                            break
                can_decode = (
                    not self.done.all() and self.replica_role != "prefill"
                )
                pf_pending = (
                    self._prefill_chunk > 0 and bool(self._prefilling.any())
                )
                if can_decode or pf_pending:
                    # pf_pending dispatches even on a prefill-role replica
                    # (its chunked prefills advance ONLY through the fused
                    # program; the decode half is vacuous there) and
                    # bypasses speculation (a draft dispatch carries no
                    # prefill half — drafting resumes once no slot is
                    # mid-prefill)
                    if self.spec is not None and not pf_pending:
                        drafts, dlens = self._collect_drafts()
                        if int(dlens.max()) > 0:
                            self._dispatch_spec(drafts, dlens)
                        else:
                            # graceful degradation: every live slot's
                            # controller has drafting off (or nothing
                            # matched) — plain chunk scan at full speed;
                            # disabled slots re-probe on schedule
                            self._dispatch_chunk()
                    else:
                        self._dispatch_chunk()
                    if self.async_depth == 0:
                        # events is always [] here: sync mode harvested
                        # at the END of the previous step
                        events = self._harvest()
            except Exception:
                # a step that fails past the fault hook (a real failure of
                # the harvest, an admission or the enqueue) orphans any
                # in-flight dispatch: its results must never surface
                # later — the caller snapshots from the last HARVESTED
                # state, and failover replay regenerates the lost tokens
                self._inflight = None
                raise
            live = ~self.done
            spent, programs = trace.compiled()
            compile_s = spent - compile_s
            self._stat_compile_s += compile_s
            self._stat_compilations += programs - compiled
            sp.set(
                alive=int(live.sum()),
                live_tokens=int(self.pos[live].sum()),
                wait_s=self._wait_this_step,
                admit_s=self._admit_this_step,
                overlap_s=self._overlap_this_step,
                compile_s=compile_s,
            )
            if self._hybrid:
                sp.set(
                    pages_full=self.allocator.used_pages,
                    pages_window=self.rings.pages_held,
                    window_pages_freed=self._window_freed_this_step,
                )
            if self._latent:
                sp.set(
                    pages_latent=self.allocator.used_pages,
                    latent_cells=self._latent_cells,
                )
            if self._diff is not None:
                sp.set(**self._diff)
            if self._moe_pairs is not None and self.cfg.n_experts > 0:
                pairs = self._moe_pairs
                sp.set(
                    moe_steps=self._moe_steps,
                    moe_pairs=int(pairs.sum()),
                    moe_max_load=int(pairs.max()),
                    moe_mean_load=float(pairs.mean()),
                )
                if self.cfg.experts_held:
                    # this chip's share: the pairs that landed on the
                    # experts held here, of those routed anywhere
                    # (every slot routes top_k a layer and step), and
                    # the (layer, step, expert) triples that got one
                    routed = (
                        self._moe_steps * self.n_slots
                        * self.cfg.moe_top_k * self.cfg.n_moe_layers
                    )
                    sp.set(
                        moe_held_pairs=int(pairs.sum()),
                        moe_routed_pairs=routed,
                        moe_experts_touched=self._moe_touched,
                    )
                    self._moe_held_total += int(pairs.sum())
                    self._moe_routed_total += routed
        self.last_step_s = sp.dur_s
        self._stat_host_ms += (sp.dur_s - self._wait_this_step) * 1e3
        return events

    def _dispatch_chunk(self) -> None:
        if self._prefill_chunk > 0 and self._prefilling.any():
            self._dispatch_interleaved()
            return
        d = self._dev
        k = self._next_chunk_len()
        if self._block:
            with self._dispatch_span(chunk=k):
                pool, blk, msk, prev, pos, done, *per_forward = (
                    self._run_chunk(
                        self.page_pool, self._table, self.params,
                        d["blk"], d["msk"], d["prev"], d["pos"],
                        d["done"], d["limit"], k,
                    )
                )
                self.page_pool = pool
                d.update(blk=blk, msk=msk, prev=prev, pos=pos, done=done)
                self._enqueue_fetch(
                    _Inflight(
                        kind="blocks",
                        arrays=(msk, pos, done, *per_forward),
                        dispatched_at=0.0,
                        old_pos=self.pos.copy(),
                        version=self._weight_version,
                    )
                )
            return
        with self._dispatch_span(chunk=k):
            rings = ()
            if self._hybrid:
                self._hold_rings(k)
                rings = (self.rings.table.copy(),)
            kv, tok, pos, done, keys, emitted, *pairs = self._run_chunk(
                *self._kv_operands(), self.params,
                d["tok"], d["pos"], d["done"], d["limit"], d["keys"],
                k, *rings, **self._adapter_args(),
            )
            self._set_kv(kv)
            d.update(tok=tok, pos=pos, done=done, keys=keys)
            # live steps form a prefix of the chunk (done is sticky), and
            # pos advances once per live step — at harvest the first
            # (new_pos - old_pos) emitted entries are exactly the real
            # tokens, whatever their values
            self._enqueue_fetch(
                _Inflight(
                    kind="chunk",
                    # the experts' routed pairs, where there are any,
                    # ride the one fetch with the tokens
                    arrays=(tok, pos, done, keys, emitted, *pairs),
                    dispatched_at=0.0,
                    old_pos=self.pos.copy(),
                    version=self._weight_version,
                )
            )

    def _kv_operands(self) -> tuple:
        """What leads every program's operands: the page pool and the
        slots' page table, or the dense bank. The first of them is
        donated, and comes back first (`_set_kv`)."""
        if self._paged:
            return (self.page_pool, self._table)
        return (self.cache,)

    def _set_kv(self, kv) -> None:
        if self._paged:
            self.page_pool = kv
        else:
            self.cache = kv

    def _pf_chunk_len(self, rem: int) -> int:
        """Tokens of prefill this dispatch carries: prefill_chunk,
        shortened on the tail — quantized DOWN to a power of two so
        the tail costs at most log2(prefill_chunk) extra compiles
        (each distinct chunk length is its own traced program), and
        NEVER padded: a padded tail would scatter pad-token K/V into
        real cells (paged: into owned pages), which no mask could
        make dead."""
        c = min(self._prefill_chunk, rem)
        k = 1
        while k * 2 <= c:
            k *= 2
        return k

    def _dispatch_interleaved(self) -> None:
        """One fused dispatch: up to prefill_chunk prompt tokens of
        the OLDEST mid-prefill slot (FIFO by request idx — one slot
        per dispatch keeps the budget bounded) plus the usual k-step
        decode scan over every live slot. When the chunk reaches the
        prompt end the slot flips to decoding before the results are
        even harvested — the flip is host bookkeeping plus one state
        scatter that chains onto this dispatch's outputs."""
        d = self._dev
        k = self._next_chunk_len()
        slot = min(
            (
                s for s in range(self.n_slots)
                if self._prefilling[s]
            ),
            key=lambda s: self.slot_req[s].idx,
        )
        req = self.slot_req[slot]
        p = len(req.prompt)
        start = int(self._frontier[slot])
        plen = self._pf_chunk_len(p - start)
        ptoks = jnp.asarray(req.prompt[start:start + plen])
        with self._dispatch_span(chunk=k, prefill_tokens=plen):
            kv, tok, pos, done, keys, frontier, emitted = self._run_pf(
                *self._kv_operands(), self.params,
                d["tok"], d["pos"], d["done"], d["limit"], d["keys"],
                d["frontier"], k, ptoks, slot, start,
                **self._adapter_args(),
            )
            self._set_kv(kv)
            d.update(
                tok=tok, pos=pos, done=done, keys=keys, frontier=frontier
            )
            # which slots are mid-prefill DURING this dispatch — captured
            # BEFORE the flip: harvest must treat their fetched done=True
            # as the freeze (not a finish) and their fetched keys as
            # drift (the scan splits every row's key, frozen or not)
            pf = self._prefilling.copy()
            # the host mirror is dispatch-authoritative (the value is
            # host-deterministic — start + plen); the fetched device copy
            # is never folded back, so an async harvest of dispatch N-1
            # cannot regress the frontier eagerly advanced for N
            self._frontier[slot] = start + plen
            self._prefill_chunks_total += 1
            if start + plen >= p:
                self._flip_to_decode(slot)
            self._enqueue_fetch(
                _Inflight(
                    kind="chunk",
                    arrays=(tok, pos, done, keys, emitted),
                    dispatched_at=0.0,
                    old_pos=self.pos.copy(),
                    version=self._weight_version,
                    pf_mask=pf,
                )
            )

    def _flip_to_decode(self, slot: int) -> None:
        """The frontier reached the prompt end: leave the mid-prefill
        lifecycle state. Colocated/decode roles re-arm the slot with
        the SAME admission scatter a blocking admission uses — and
        the ORIGINAL admission key: the frozen rows rode the decode
        scans, whose _advance split EVERY row's key, so the drifted
        device key must be re-seeded or sampled output diverges from
        the blocking oracle. Prefill-role replicas stay frozen (they
        must never decode) and park the request for export instead —
        frontier == prompt end IS this role's export gate."""
        req = self.slot_req[slot]
        self._prefilling[slot] = False
        self.slot_key[slot] = req.prng_key
        if self.replica_role != "prefill":
            d = self._dev
            d["tok"], d["pos"], d["done"], d["limit"], d["keys"] = (
                _state_admit_prog(
                    d["tok"], d["pos"], d["done"], d["limit"],
                    d["keys"], slot, int(self.tok[slot]),
                    int(self.pos[slot]), int(self.limit[slot]),
                    self.slot_key[slot],
                )
            )
        else:
            self._parked[slot] = True
            self._prefill_ready.append(req)

    def _clear_prefill(self, slot: int) -> None:
        """Release-path cleanup of the mid-prefill state. No device
        scatter: a freed slot's stale device frontier is dead exactly
        like a stale table row — the dispatcher only reads entries it
        set at admission, and the slot is already frozen."""
        self._prefilling[slot] = False
        self._parked[slot] = False
        self._frontier[slot] = 0

    def _collect_drafts(self):
        """Host drafting pass, batched in speculative.py: the per-slot
        proposal loop runs only over live slots and the padded [B, K]
        assembly is vectorized (draft_batch), so the step hot path no
        longer pays an O(n_slots) Python loop per dispatch."""
        return self.spec.draft_batch(self.done)

    def _dispatch_spec(
        self, drafts: np.ndarray, dlens: np.ndarray
    ) -> None:
        d = self._dev
        with self._dispatch_span(draft_len=int(dlens.max())):
            (
                kv, tok, pos, done, keys, emitted, n_emit, accepted
            ) = self._run_spec(
                *self._kv_operands(), self.params,
                d["tok"], d["pos"], d["done"], d["limit"], d["keys"],
                jnp.asarray(drafts), jnp.asarray(dlens),
                **self._adapter_args(),
            )
            self._set_kv(kv)
            d.update(tok=tok, pos=pos, done=done, keys=keys)
            self._enqueue_fetch(
                _Inflight(
                    kind="spec",
                    arrays=(
                        tok, pos, done, keys, emitted, n_emit, accepted
                    ),
                    dispatched_at=0.0,
                    dlens=dlens,
                    was_live=~self.done,
                    version=self._weight_version,
                )
            )

    def _enqueue_fetch(self, pend: _Inflight) -> None:
        _start_host_copy(pend.arrays)
        self._inflight = pend

    @contextlib.contextmanager
    def _dispatch_span(self, **counts):
        """The `engine.dispatch` span around one enqueue; its end is
        also the in-flight record's `dispatched_at` (the start of the
        device span `_harvest` measures the overlap against)."""
        with trace.span("engine.dispatch", **counts) as sp:
            yield
        self._inflight.dispatched_at = sp.t0 + sp.dur_s

    def _harvest(self) -> List[StepEvent]:
        """Complete the in-flight dispatch's host copies, refresh the
        mirrors, and turn its outputs into events. [] when nothing is
        in flight. The wait measured here is the step BUBBLE: device
        time the host had nothing to overlap with."""
        pend = self._inflight
        self._inflight = None
        if pend is None:
            return []
        with trace.span("engine.harvest") as sp:
            host = _to_host(*pend.arrays)
            w1 = time.perf_counter()
            wait_s = w1 - sp.t0
            sp.set(wait_s=wait_s)
            # the device span runs from the enqueue to here; what of
            # it the host did not spend waiting, it spent on other work
            span_s = w1 - pend.dispatched_at
            hidden_s = max(span_s - wait_s, 0.0)
            self._wait_this_step += wait_s
            self._overlap_this_step += hidden_s
            self._stat_wait_ms += wait_s * 1e3
            self._stat_span_ms += span_s * 1e3
            self._stat_overlap_ms += hidden_s * 1e3
            self._stat_dispatches += 1
            if pend.kind == "blocks":
                msk, pos, done, took, ids, phase, fused, *pairs = host
                if pairs:
                    self._moe_pairs = pairs[0]
                    self._moe_steps = phase.shape[1]
                self.msk, self.pos = msk, pos
                return self._emit_block_events(
                    took, ids, phase, fused, pend.old_pos, done,
                    pend.version,
                )
            if pend.kind == "chunk":
                tok, pos, done, keys, emitted, *pairs = host
                if pairs:
                    self._moe_pairs = pairs[0]
                    if self._moe_pairs.ndim == 2:  # a held share
                        self._moe_touched = int(self._moe_pairs[1].sum())
                        self._moe_pairs = self._moe_pairs[0]
                    self._moe_steps = emitted.shape[1]
                counts = pos - pend.old_pos
                if self._latent:
                    # a slot that took n steps from position p read
                    # p + 1 .. p + n rows in every layer
                    n = counts.astype(np.int64)
                    self._latent_cells = int(self.cfg.n_layers * np.sum(
                        n * pend.old_pos + n * (n + 1) // 2
                    ))
            else:
                tok, pos, done, keys, emitted, n_emit, accepted = host
                counts = n_emit
                for slot in range(self.n_slots):
                    if pend.was_live[slot]:
                        self.spec.record(
                            slot,
                            int(pend.dlens[slot]),
                            int(accepted[slot]),
                            int(n_emit[slot]),
                        )
            self.tok, self.pos, self.slot_key = tok, pos, keys
            if pend.pf_mask is not None:
                # slots that were mid-prefill during this dispatch: the
                # fetched key is drift (the scan split every row's key,
                # frozen or not) — the journal and preempt-replay read
                # the key mirror, so re-assert the ORIGINAL admission key
                for slot in range(self.n_slots):
                    if pend.pf_mask[slot]:
                        req = self.slot_req[slot]
                        if req is not None and req.prng_key is not None:
                            self.slot_key[slot] = req.prng_key
            return self._emit_events(
                emitted, counts, done, pend.version, pend.pf_mask
            )

    def _emit_events(
        self, emitted: np.ndarray, counts: np.ndarray,
        new_done: np.ndarray, version: int = 0,
        pf_mask: Optional[np.ndarray] = None,
    ) -> List[StepEvent]:
        """Shared post-dispatch bookkeeping: `counts[slot]` leading
        entries of `emitted[slot]` are the slot's real new tokens.
        pf_mask marks slots that were MID-PREFILL when the dispatch
        was built: their fetched done=True is the admission freeze,
        not a finish (counts is 0 for them — a frozen row's pos never
        advances), so they must neither emit nor release."""
        events: List[StepEvent] = []
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None or req.done:
                continue
            if pf_mask is not None and pf_mask[slot]:
                continue
            if self._parked[slot]:
                # prefill-role: done=True is the park freeze, not a
                # finish — the pages must survive until export
                continue
            new_toks = [
                int(t) for t in emitted[slot][: int(counts[slot])]
            ]
            req.out.extend(new_toks)
            if new_toks:
                # one dispatch carries one version: the set grows past
                # a single entry only across an opted-in live swap
                req.versions.add(version)
            if self.spec is not None and new_toks:
                # whichever path emitted them, the drafter's context
                # must see every token or proposals go stale
                self.spec.extend(slot, new_toks)
            finished = bool(new_done[slot])
            if finished:
                req.done = True
                if self._paged:
                    # free the run immediately (not at retire): the
                    # tokens are on host, the KV is dead — the pages
                    # back the NEXT admission. The programs already
                    # route this done row's rewrites to trash.
                    self._release_slot_pages(slot)
                if self.prefix_cache is not None:
                    self._release_slot_row(slot)
            if new_toks or finished:
                events.append((req.idx, new_toks, finished))
        self._settle_done(new_done, pf_mask)
        return events

    def _emit_block_events(
        self, took: np.ndarray, ids: np.ndarray, phase: np.ndarray,
        fused: np.ndarray, old_pos: np.ndarray, new_done: np.ndarray,
        version: int = 0,
    ) -> List[StepEvent]:
        """`_emit_events` where a forward does not yield one token: of
        a slot's k forwards (`phase` [B, k]: 0 a done row's, 1 a
        denoising forward that left its block unfinished, 2 one that
        FINISHED it) each finishing one hands over its block's ids
        (`ids` [B, k, block]: they are final then, though their keys
        and values reach the pool with the slot's next forward), in
        order, less the prompt's own at the head of a request's first
        block and those past its limit in its last. A dispatch in
        which a slot finished no block leaves no event for it."""
        block = self._block
        commits = phase == 2
        alive = phase > 0
        # the block each forward ran: `old_pos` plus a block a
        # finished block before it
        start = old_pos[:, None] + block * (
            np.cumsum(commits, axis=1) - commits
        )
        events: List[StepEvent] = []
        n_tokens = 0
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None or req.done:
                continue
            p, limit = len(req.prompt), int(self.limit[slot])
            new_toks: List[int] = []
            for f in np.flatnonzero(commits[slot]):
                s0 = int(start[slot, f])
                new_toks.extend(
                    ids[slot, f, max(p - s0, 0): limit - s0].tolist()
                )
            req.out.extend(new_toks)
            n_tokens += len(new_toks)
            if new_toks:
                req.versions.add(version)
            finished = bool(new_done[slot])
            if finished:
                req.done = True
                self._release_slot_pages(slot)
            if new_toks or finished:
                events.append((req.idx, new_toks, finished))
        idxs = np.fromiter(
            (-1 if r is None else r.idx for r in self.slot_req),
            np.int64, self.n_slots,
        )
        occupied = (idxs >= 0)[:, None]
        live = alive & occupied
        self._diff = dict(
            diff_forwards=int(live.sum()),
            # the live forwards that carried a finished block
            diff_fused=int((fused & live).sum()),
            diff_commits=int((commits & occupied).sum()),
            diff_tokens=n_tokens,
            # a live forward reads its slot's cells up to its block's
            # end, in every layer
            diff_cells=int(
                self.cfg.n_layers * ((start + block) * live).sum()
            ),
        )
        self._diff_forwards_total += self._diff["diff_forwards"]
        self._diff_tokens_total += n_tokens
        if self.record_blocks:
            self.block_log.append((idxs, start, phase, took, ids))
        self._settle_done(new_done, None)
        return events

    def block_trajectories(self) -> Dict[int, List[tuple]]:
        """What `record_blocks` kept, a request: idx -> rows in order,
        (the block's first position, phase, ids). Every forward leaves
        a phase-1 row, the ids it unmasked (-1 elsewhere); the forward
        that finished a block leaves a phase-2 row after it, the
        block's ids. So every block reads as its denoising rows and
        then ONE row of what was committed, whichever forward stored
        its keys and values."""
        out: Dict[int, List[tuple]] = {}
        for idxs, start, phase, took, ids in self.block_log:
            for slot in np.flatnonzero(idxs >= 0):
                rows = out.setdefault(int(idxs[slot]), [])
                for f in np.flatnonzero(phase[slot]):
                    s0 = int(start[slot, f])
                    rows.append((s0, 1, took[slot, f].tolist()))
                    if phase[slot, f] == 2:
                        rows.append((s0, 2, ids[slot, f].tolist()))
        return out

    def _settle_done(
        self, new_done: np.ndarray, pf_mask: Optional[np.ndarray]
    ) -> None:
        """The done mirror after a dispatch's events."""
        self.done = new_done
        # a cancel that landed while this dispatch was in flight set
        # the mirror before the dispatch's (older) done could overwrite
        # it — re-assert it, or the freed slot would resurrect (the
        # device copy already carries the cancel: its scatter chained
        # onto this dispatch's output)
        for slot in range(self.n_slots):
            if self.slot_req[slot] is None:
                self.done[slot] = True
            elif (pf_mask is not None and pf_mask[slot]) or (
                self._parked[slot]
            ):
                # the fetched done carried the admission/park freeze;
                # the HOST mirror's truth is "occupied" — without
                # this the scheduler would re-admit over a
                # mid-prefill (or awaiting-export) slot
                self.done[slot] = False

    def retire(self, idx: int) -> np.ndarray:
        """Drop a request from the ledger and return its continuation
        — the streaming path's per-request counterpart of
        generate_all()'s end-of-drain cleanup (without it a long-lived
        serving engine retains every request ever served)."""
        if idx not in self._pending:
            raise KeyError(f"request {idx} is not pending")
        del self._pending[idx]
        req = self._requests.pop(idx)
        # one-step slot cleanup: whatever path got us here (normal
        # finish, publish-back failure, scheduler-side abandonment),
        # retire leaves NO pinned prefix row, page run, or slot
        # occupancy behind — a failed publish must never leak a ref
        # count until LRU pressure finds it
        for slot in range(self.n_slots):
            if self.slot_req[slot] is req:
                self.slot_req[slot] = None
                self.done[slot] = True
                self._dev["done"] = _state_cancel_prog(
                    self._dev["done"], slot
                )
                if self._paged:
                    self._release_slot_pages(slot)
                if self.prefix_cache is not None:
                    self._release_slot_row(slot)
                self._clear_prefill(slot)
        try:
            self._prefill_ready.remove(req)
        except ValueError:
            pass
        if req.adapter_id is not None:
            # unpin the adapter slot with the ledger entry: residency
            # survives (that is the cache), the slot just becomes
            # evictable once no other request references it
            self._adapter_cache.release(req.adapter_id)
        return np.asarray(req.out, np.int32)

    def take_prefilled(self) -> List[_Request]:
        """Drain the prefill-role completion queue: requests whose
        prompt KV is resident and exportable. Each is still live in
        its slot (the caller exports via serving/handoff.py and then
        retire()s it — the export must happen before the slot's pages
        can be reused)."""
        out, self._prefill_ready = self._prefill_ready, []
        return out

    def cancel(self, idx: int) -> None:
        """Abort a request wherever it is — still queued or live in a
        slot (client disconnected mid-stream). Frees the slot for the
        next admission and releases any pinned prefix-cache row; a
        no-op for unknown/already-retired indices."""
        req = self._requests.pop(idx, None)
        self._pending.pop(idx, None)
        if req is None:
            return
        try:
            self._queue.remove(req)
        except ValueError:
            pass
        try:
            self._prefill_ready.remove(req)
        except ValueError:
            pass
        req.done = True
        for slot in range(self.n_slots):
            if self.slot_req[slot] is req:
                self.done[slot] = True
                # one scatter onto the CURRENT device done — if a
                # dispatch is in flight this chains after it, so the
                # slot is freed on device no later than the harvest
                # that frees it on host
                self._dev["done"] = _state_cancel_prog(
                    self._dev["done"], slot
                )
                self.slot_req[slot] = None
                if self._paged:
                    self._release_slot_pages(slot)
                if self.prefix_cache is not None:
                    self._release_slot_row(slot)
                self._clear_prefill(slot)
                break
        if req.adapter_id is not None:
            self._adapter_cache.release(req.adapter_id)

    def request_progress(self, idx: int) -> Optional[int]:
        """Preemption coldness of a live request, from the host
        mirrors — the scheduler's coldest-victim choice for admission
        preemption reads this so its notion of "least progress" is
        the engine's own (the same _slot_progress quantity
        _pick_preempt_slot orders by). Mid-decode: pos, the resident
        KV cells (>= 0). Mid-prefill: NEGATIVE — frontier minus
        prompt length, the cells still owed — so a
        prefilled-but-unemitted slot always ranks colder than any
        decoding one. None when the request is not occupying a slot
        (still engine-queued: zero footprint)."""
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is not None and not self.done[slot] and req.idx == idx:
                return self._slot_progress(slot)
        return None

    def live_request_keys(self) -> Dict[int, np.ndarray]:
        """idx -> current per-slot PRNG key for every live request —
        the scheduler journals these after each pump so a failover
        re-admission continues the exact key stream."""
        out: Dict[int, np.ndarray] = {}
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is not None and not req.done:
                out[req.idx] = self.slot_key[slot].copy()
        return out

    def reset(self) -> None:
        """Rebuild device state from scratch after a crash. A real
        mid-dispatch failure can leave the donated cache buffer
        invalid, so restart never trusts it: the KV bank (and prefix
        pool/radix, and spec drafter state) are re-created, the queue
        and ledger dropped. Request indices stay monotonic so stale
        events can never alias a new request. Compiled programs are
        untouched — they're cached per (config, knobs), not per
        engine state."""
        if self._paged:
            # the donated pool buffer is as untrustworthy as a donated
            # dense bank — rebuild pool, allocator, and tables, and
            # drop every host-side run record with them
            self.allocator = PageAllocator(self.n_pages, self.page_size)
            if self._hybrid:
                self.page_pool = init_hybrid_pools(
                    self.cfg, self.n_pages, self._mint_window_class(),
                    self.page_size,
                )
            else:
                self.page_pool = self._shard_bank(
                    init_page_pool(
                        self.cfg, self.n_pages, self.page_size,
                        quant=self._kv_quant,
                    )
                )
            self._table = self._replicate(
                jnp.zeros(
                    (self.n_slots, self._pages_per_slot), jnp.int32
                )
            )
            self._slot_pages = [[] for _ in range(self.n_slots)]
            self._row_pages = {}
        else:
            self.cache = self._shard_bank(
                init_kv_cache(
                    self.cfg,
                    self.n_slots,
                    self.max_len + self.spec_draft_len,
                    quant=self._kv_quant,
                )
            )
        self.tok[:] = self.pad_id
        self.pos[:] = 0
        self.limit[:] = 0
        self.done[:] = True
        self.slot_key[:] = 0
        self.adapt[:] = 0
        self.blk[:] = 0
        self.msk[:] = False
        self.block_log = []
        # mid-prefill lifecycle state dies with the slots (the stall
        # and chunk counters survive: they are cumulative telemetry)
        self._prefilling[:] = False
        self._parked[:] = False
        self._frontier[:] = 0
        if self._adapter_cache is not None:
            # drop every ledger pin (the ledger itself is dropped
            # below) and re-mint the bank: a crash mid-upload leaves
            # the donated bank as untrustworthy as the KV banks.
            # rebuild() re-uploads residents from the host registry.
            for req in self._requests.values():
                if req.adapter_id is not None:
                    self._adapter_cache.release(req.adapter_id)
            self._adapter_cache.rebuild()
        # fresh device copies too — the crash may have struck with a
        # dispatch in flight; its outputs (and the in-flight record)
        # must never leak into the restarted engine
        self._dev = self._device_state()
        self._inflight = None
        if self.kv_tier is not None:
            # a crash mid-demotion may have left staging buffers whose
            # producing dispatch died with the engine — drop every
            # entry rather than trust bytes that may never land
            self.kv_tier.clear()
        self.slot_req = [None] * self.n_slots
        self._slot_row = [None] * self.n_slots
        self._queue.clear()
        self._requests.clear()
        self._pending.clear()
        self._prefill_ready = []
        self._step_no = 0
        if self.prefix_cache is not None:
            self.prefix_cache = RadixPrefixCache(
                self._prefix_rows,
                block=self._prefix_block,
                on_evict=(
                    self._on_prefix_evict
                    if (self._paged or self.kv_tier is not None)
                    else None
                ),
            )
            self.pool = self._shard_bank(
                init_kv_cache(
                    self.cfg, self._prefix_rows, self.max_len
                )
            )
        if self.spec is not None:
            ng_max, ng_min, thresh, probe = self._spec_knobs
            self.spec = SpeculativeDecoder(
                self.n_slots,
                self.spec_draft_len,
                ngram_max=ng_max,
                ngram_min=ng_min,
                threshold=thresh,
                probe_interval=probe,
            )

    def generate_all(
        self, prompts: Sequence[Sequence[int]]
    ) -> List[np.ndarray]:
        """Run every queued prompt to completion; returns generated
        continuations (without the prompt) in submission order —
        including any requests submit()ted beforehand that have not
        been returned yet. Callable repeatedly."""
        for pr in prompts:
            self.submit(pr)
        while self.has_work():
            self.step()
        # drain complete: drop the request ledger, or a long-lived
        # engine (e.g. one PPO trainer across 100k rollouts) retains
        # every prompt + output list ever served and leaks host RAM
        out = []
        for i in self._pending:
            req = self._requests.pop(i)
            if req.adapter_id is not None:
                self._adapter_cache.release(req.adapter_id)
            out.append(np.asarray(req.out, np.int32))
        self._pending = {}
        return out


# serving-facing name; ContinuousBatcher stays for the rl/ shim and
# existing callers
GenerationEngine = ContinuousBatcher
