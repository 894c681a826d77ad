"""Serving-stack bench: TTFT / TPOT / throughput through the SLO
scheduler vs the lockstep baseline.

What decode_bench.py is to the raw engine, this is to the serving
subsystem (dlrover_tpu/serving/): the same mixed-length request set is
driven (a) through `RequestScheduler` + `ContinuousBatcher` — the path
a gateway request takes, minus the HTTP framing — and (b) through
lockstep `decode.generate` one batch at a time. The published number
is served tokens/s; `vs_baseline` is the continuous/lockstep ratio
(slot re-admission is the whole serving win at mixed lengths).

A second phase drives the shared-system-prompt workload (every request
= one common system prefix + a short unique tail — the
millions-of-users fleet shape) twice: prefix cache OFF (cold TTFT) and
ON (warm TTFT + hit rate). The cache's win is admission-time: warm
admissions prefill only the suffix bucket, so warm TTFT p50 must sit
strictly below cold.

A third phase drives an n-gram-friendly echo workload (each prompt
contains the model's own greedy repetition loop) through the engine
twice — spec_draft_len=0 (baseline) and spec_draft_len=K, both at
chunk=1 so the baseline is the literature's one-token-per-step decode
(chunk-scan amortization is the MAIN phase's metric, not this one) —
and publishes acceptance, accepted-per-step, and the TPOT p50 pair.
The contract lock: speculation must accept >1 draft token per verify
round AND beat the one-step baseline TPOT, or it is dead weight.

A fourth phase measures the async double-buffered dispatch
(`async_depth=1`): the main mixed-length workload runs once
synchronous and once pipelined one dispatch deep, publishing the TPOT
p50 pair plus the engine's overlap ratio (fraction of device span
hidden behind host work). The contract lock: async TPOT p50 strictly
below sync, overlap ratio > 0, and greedy byte-parity between depths
across ALL engine variants (plain, int8 KV, prefix cache,
speculative).

A fifth phase drives the same mixed-length set through a TWO-replica
pool twice: a steady pass (async_depth=0), then a chaos pass at
async_depth=1 where a FaultInjector kills replica-0 mid-decode. The
contract lock: chaos success rate is exactly 1.0 (zero admitted
requests lost — stranded work fails over and resumes by replay),
greedy outputs stay byte-identical to the steady pass even across the
pipelining depths, and the chaos TTFT p99 stays within a bounded
multiple of steady-state (failover costs one re-prefill, not a retry
storm).

A sixth phase exercises the paged KV layout (kv_layout="paged"):
(a) the main mixed-length workload runs scheduler-driven on a paged
engine with a dense-equivalent pool — the TPOT p50 pair against the
dense bank locks the paging overhead (gather + table bookkeeping)
under 10%; (b) the same set drains on a pool a FRACTION of the dense
footprint, forcing preempt-and-swap — the lock is success rate 1.0
with byte parity to the dense outputs (oversubscription costs
latency, never correctness); (c) the shared-system-prompt set warms a
paged+prefix engine — warm suffix admissions must share prefix pages
by refcount with ZERO copy-on-write (CoW is confined to the
full-prefix admission frontier, which this workload never hits).

Run (real chip):  python benchmarks/serve_bench.py
CPU smoke:        DLROVER_TPU_FORCE_CPU=1 python benchmarks/serve_bench.py
Prints ONE JSON line (the schema tests/test_bench_contract.py pins):
metric/value/unit/vs_baseline + detail{ttft_ms_p50, ttft_ms_p95,
tpot_ms_mean, throughput_tok_s, n_requests, shed_total,
prefix_hit_rate, ttft_cold_ms_p50, ttft_warm_ms_p50, ...}.
"""

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.utils.platform import (  # noqa: E402
    FORCE_CPU_ENV,
    ensure_cpu_if_forced,
)

# The mesh phase needs >1 local device to exercise tp=2; on a forced-CPU
# smoke run ask XLA for 8 virtual host devices. Must happen before the
# first jax import (ensure_cpu_if_forced imports jax), and must not
# clobber an operator-supplied flag set.
if os.environ.get(FORCE_CPU_ENV) == "1" and (
    "xla_force_host_platform_device_count"
    not in os.environ.get("XLA_FLAGS", "")
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

ensure_cpu_if_forced()


def main():
    from dlrover_tpu.analysis import bench_preflight

    bench_preflight("serve_bench.py")

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import decode, llama
    from dlrover_tpu.serving.engine import ContinuousBatcher
    from dlrover_tpu.serving.metrics import ServingMetrics
    from dlrover_tpu.serving.scheduler import (
        RequestScheduler,
        SloConfig,
    )

    # a TPU, or the explicit CPU smoke mode (DLROVER_TPU_FORCE_CPU=1:
    # tiny model, a line that names backend "cpu", a check that the
    # script runs — never a measurement); anything else is an error.
    # One process: nothing below starts a child that needs the chip.
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and os.environ.get(FORCE_CPU_ENV) != "1":
        sys.exit(
            f"serve_bench.py measures a TPU and found platform "
            f"{platform!r}; set {FORCE_CPU_ENV}=1 for the explicit "
            "CPU smoke mode"
        )

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=24, n_heads=8,
            n_kv_heads=8, mlp_dim=4096, max_seq_len=2048,
            remat=False, attn_impl="auto",
        )
        n_requests, n_slots, max_new, max_len, chunk = 48, 8, 128, 1024, 8
        len_lo, len_hi = 16, 512
    else:
        import dataclasses

        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(), dtype=jnp.float32
        )
        n_requests, n_slots, max_new, max_len, chunk = 12, 4, 10, 64, 4
        len_lo, len_hi = 3, 20

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = rng.integers(len_lo, len_hi, size=n_requests)
    prompts = [
        rng.integers(1, min(250, cfg.vocab_size), size=n).tolist()
        for n in lens
    ]

    # ---- continuous path: scheduler over the slot engine ----------------
    metrics = ServingMetrics()
    engine = ContinuousBatcher(
        cfg, params, n_slots=n_slots, max_len=max_len,
        max_new_tokens=max_new, chunk=chunk, pad_id=-1,
    )
    slo = SloConfig(
        max_queue_depth=n_requests + 1,
        max_new_tokens=max_new,
        default_deadline_s=600.0,
    )
    # warm the compiled programs outside the timed region (chunk scan
    # + one prefill bucket) on a throwaway scheduler so the published
    # counters reflect only the measured request set
    warm_sched = RequestScheduler(engine, slo, metrics=ServingMetrics())
    warm = warm_sched.submit(prompts[0], max_new=2)
    warm_sched.run_to_completion()
    assert warm.state.value == "done"

    sched = RequestScheduler(engine, slo, metrics=metrics)

    reqs = [sched.submit(p, max_new=max_new) for p in prompts]
    t0 = time.monotonic()
    sched.run_to_completion()
    dt_cont = time.monotonic() - t0
    served_tokens = sum(len(r.tokens) for r in reqs)
    cont_tps = served_tokens / dt_cont

    ttfts = sorted(
        (r.first_token_ts - r.submit_ts) * 1000.0
        for r in reqs
        if r.first_token_ts is not None
    )
    tpots = [
        (r.finish_ts - r.first_token_ts) * 1000.0 / (len(r.tokens) - 1)
        for r in reqs
        if r.first_token_ts is not None and len(r.tokens) > 1
    ]

    def pct(vals, q):
        return vals[min(len(vals) - 1, int(q * len(vals)))] if vals else 0.0

    # ---- lockstep baseline: fixed batches, drain the same set -----------
    total_base_tokens = 0
    t0 = time.monotonic()
    from dlrover_tpu.serving.engine import _pad_bucket

    for i in range(0, n_requests, n_slots):
        batch = prompts[i : i + n_slots]
        # pow2-bucket the batch width like the engine's prefill does,
        # so the lockstep baseline also compiles once per bucket
        # rather than once per batch (fair steady-state comparison)
        width = min(_pad_bucket(max(len(p) for p in batch)), max_len)
        padded = np.full((len(batch), width), 0, np.int32)
        for j, p in enumerate(batch):
            padded[j, width - len(p):] = p  # left-pad to align ends
        out = decode.generate(
            cfg, params, jnp.asarray(padded), max_new,
            max_len=width + max_new,
        )
        total_base_tokens += int(np.asarray(out).shape[1] - width) * len(
            batch
        )
    dt_base = time.monotonic() - t0
    base_tps = total_base_tokens / dt_base

    # ---- shared-system-prompt workload: prefix cache off vs on ----------
    # A model big enough that prefill FLOPs dominate dispatch overhead
    # even on the CPU smoke path — the cache's win IS skipped prefill,
    # so a dispatch-bound toy would only measure noise.
    if on_tpu:
        pcfg = cfg
        p_max_len, sys_len, tail_lo, tail_hi = 1024, 512, 8, 64
        n_prefix_reqs, p_slots, p_max_new, p_chunk = 32, 8, 32, 8
    else:
        import dataclasses

        pcfg = dataclasses.replace(
            llama.LlamaConfig.tiny(), dtype=jnp.float32,
            dim=128, n_heads=4, n_kv_heads=2, mlp_dim=512,
            vocab_size=512, max_seq_len=512,
        )
        p_max_len, sys_len, tail_lo, tail_hi = 512, 448, 2, 16
        n_prefix_reqs, p_slots, p_max_new, p_chunk = 12, 2, 8, 4

    pparams = llama.init_params(pcfg, jax.random.PRNGKey(1))
    sys_prompt = rng.integers(
        1, min(500, pcfg.vocab_size), size=sys_len
    ).tolist()
    tails = [
        rng.integers(
            1, min(500, pcfg.vocab_size),
            size=int(t),
        ).tolist()
        for t in rng.integers(tail_lo, tail_hi, size=n_prefix_reqs)
    ]
    shared_prompts = [sys_prompt + t for t in tails]

    def _ttft_pass(rows):
        """Drive the shared-prefix set one request at a time (TTFT =
        admission + first chunk, no queue wait) and return per-request
        TTFTs + the engine. Warm-up requests compile every program —
        and, when the cache is on, prime the pool — outside the timed
        region."""
        eng = ContinuousBatcher(
            pcfg, pparams, n_slots=p_slots, max_len=p_max_len,
            max_new_tokens=p_max_new, chunk=p_chunk, pad_id=-1,
            prefix_cache_rows=rows,
        )
        sched = RequestScheduler(
            eng,
            SloConfig(
                max_queue_depth=n_prefix_reqs + 2,
                max_new_tokens=p_max_new,
                default_deadline_s=600.0,
            ),
            metrics=ServingMetrics(),
        )
        # warm-up 1: cold-path compile — the bare system prompt, so
        # the published prefix depth is exactly sys_len (a tailed
        # prompt could block-align DEEPER than the shared prefix and
        # the next request would miss it). Full max_new so every
        # chunk-scan length the timed requests need compiles here.
        sched.submit(sys_prompt, max_new=p_max_new)
        sched.run_to_completion()
        # warm-up 2: warm-path compile (suffix bucket + install)
        sched.submit(shared_prompts[1], max_new=p_max_new)
        sched.run_to_completion()
        ttfts = []
        for p in shared_prompts:
            r = sched.submit(p, max_new=p_max_new)
            sched.run_to_completion()
            ttfts.append((r.first_token_ts - r.submit_ts) * 1000.0)
        return sorted(ttfts), eng

    cold_ttfts, _ = _ttft_pass(rows=0)
    warm_ttfts, warm_eng = _ttft_pass(rows=8)
    pc_stats = warm_eng.prefix_cache.stats()

    # ---- speculative phase: n-gram-friendly workload, spec off vs on ----
    # The drafter's target regime is generation that revisits seen
    # text. The portable stand-in: a tiny-vocab model driven by its
    # own greedy echo — each prompt is a seed plus the model's own
    # continuation, kept only when that trajectory has settled into a
    # repetition loop (the cycle is IN the prompt, so prompt-lookup
    # drafting predicts the continuation the way it would on
    # templated/retrieval text). Tiny-vocab on every backend: the
    # phase measures speculation dynamics (acceptance, tokens/step,
    # TPOT), which don't need model scale.
    import dataclasses as _dc

    scfg = _dc.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32, vocab_size=32
    )
    sparams = llama.init_params(scfg, jax.random.PRNGKey(2))
    spec_k, s_max_new, seed_len, echo_len = 8, 48, 6, 160
    # chunk=1 for BOTH passes: the spec-decoding comparison is verify
    # vs ONE-token-per-step decode (the literature's baseline). The
    # chunk scan is a separate amortization the main phase already
    # measures — and with dispatch overhead gone device-resident
    # (async phase below), a chunk=4 scan on a CPU-sized model beats
    # speculation on raw compute (a K+1-wide verify costs ~K+1 tiny
    # forwards here; on a real chip it costs ~one memory-bound step)
    n_spec_reqs, s_slots, s_chunk = 8, 2, 1
    s_max_len = seed_len + echo_len + s_max_new + spec_k + 4

    def _has_cycle(gen):
        return any(
            len(gen) >= 3 * p
            and gen[-p:] == gen[-2 * p : -p] == gen[-3 * p : -2 * p]
            for p in range(1, 33)
        )

    spec_prompts = []
    tries = 0
    srng = np.random.default_rng(0)  # phase-local: workload must not
    # drift when an earlier phase changes its rng draws
    while len(spec_prompts) < n_spec_reqs and tries < 64:
        tries += 1
        seed = srng.integers(1, scfg.vocab_size, size=seed_len).tolist()
        echo = np.asarray(
            decode.generate(
                scfg, sparams, jnp.asarray([seed], jnp.int32),
                echo_len, max_len=seed_len + echo_len,
            )
        )[0].tolist()
        if _has_cycle(echo[seed_len:]):
            spec_prompts.append(echo)

    def _spec_pass(draft_len):
        """Drain the echo workload through the scheduler; returns
        per-request TPOTs + the engine (for spec counters)."""
        eng = ContinuousBatcher(
            scfg, sparams, n_slots=s_slots, max_len=s_max_len,
            max_new_tokens=s_max_new, chunk=s_chunk, pad_id=-1,
            spec_draft_len=draft_len, spec_probe_interval=4,
            spec_ngram_max=4,
        )
        ssched = RequestScheduler(
            eng,
            SloConfig(
                max_queue_depth=n_spec_reqs + 6,
                max_new_tokens=s_max_new,
                default_deadline_s=600.0,
            ),
            metrics=ServingMetrics(),
        )
        # warm every program the timed drain can hit: the spec/verify
        # program, the prefill bucket, and each chunk length the
        # fallback path reaches (variable-advance slots leave 1..chunk
        # remainders, and a mid-drain compile would land in TPOT)
        for mn in (1, 2, 3, s_max_new):
            ssched.submit(spec_prompts[0], max_new=mn)
        ssched.run_to_completion()
        timed = RequestScheduler(
            eng,
            SloConfig(
                max_queue_depth=n_spec_reqs + 6,
                max_new_tokens=s_max_new,
                default_deadline_s=600.0,
            ),
            metrics=ServingMetrics(),
        )
        sreqs = [
            timed.submit(p, max_new=s_max_new) for p in spec_prompts
        ]
        timed.run_to_completion()
        stpots = sorted(
            (r.finish_ts - r.first_token_ts)
            * 1000.0
            / (len(r.tokens) - 1)
            for r in sreqs
            if r.first_token_ts is not None and len(r.tokens) > 1
        )
        return stpots, eng, [list(r.tokens) for r in sreqs]

    spec_base_tpots, _, spec_base_out = _spec_pass(0)
    spec_tpots, spec_eng, spec_out = _spec_pass(spec_k)
    # greedy parity is a hard guarantee of the verify program; a bench
    # that publishes a speedup for wrong tokens would be lying
    assert spec_out == spec_base_out, "speculative greedy parity broke"
    spec_stats = spec_eng.spec.stats()

    # ---- overlap phase: async double-buffered dispatch off vs on --------
    # Same mixed-length workload as the main phase, once at
    # async_depth=0 (every step blocks on its own dispatch) and once
    # at async_depth=1 (the host streams/journals dispatch N-1 while
    # the device runs dispatch N). The published pair is TPOT p50;
    # best-of-2 per mode because the CPU smoke competes with the OS
    # scheduler for the very cores the "device" runs on.
    def _overlap_pass(depth):
        eng = ContinuousBatcher(
            cfg, params, n_slots=n_slots, max_len=max_len,
            max_new_tokens=max_new, chunk=chunk, pad_id=-1,
            async_depth=depth,
        )
        warm = RequestScheduler(eng, slo, metrics=ServingMetrics())
        warm.submit(prompts[0], max_new=2)
        warm.run_to_completion()
        timed = RequestScheduler(eng, slo, metrics=ServingMetrics())
        oreqs = [timed.submit(p, max_new=max_new) for p in prompts]
        timed.run_to_completion()
        otpots = sorted(
            (r.finish_ts - r.first_token_ts)
            * 1000.0
            / (len(r.tokens) - 1)
            for r in oreqs
            if r.first_token_ts is not None and len(r.tokens) > 1
        )
        return pct(otpots, 0.5), eng.step_stats()["overlap_ratio"]

    sync_tpot_p50 = min(_overlap_pass(0)[0] for _ in range(2))
    async_runs = [_overlap_pass(1) for _ in range(2)]
    async_tpot_p50 = min(t for t, _ in async_runs)
    async_overlap_ratio = max(r for _, r in async_runs)

    # byte-parity sweep: depth 1 must reproduce depth 0 exactly on
    # every engine variant (plain, int8 KV, prefix cache, spec) — the
    # async mode reorders WHEN results surface, never WHAT they are
    def _parity_out(engine_kw):
        # chunk=4 (not the spec phase's 1): parity must cover the
        # multi-step chunk scan's partial-advance bookkeeping too
        eng = ContinuousBatcher(
            scfg, sparams, n_slots=s_slots, max_len=s_max_len,
            max_new_tokens=s_max_new, chunk=4, pad_id=-1,
            **engine_kw,
        )
        return [o.tolist() for o in eng.generate_all(spec_prompts)]

    async_parity_ok = all(
        _parity_out(dict(kw, async_depth=1))
        == _parity_out(dict(kw, async_depth=0))
        for kw in (
            {},
            {"kv_quant": "int8"},
            {"prefix_cache_rows": 4},
            {"spec_draft_len": spec_k, "spec_ngram_max": 4},
        )
    )

    # ---- chaos phase: replica death mid-decode, failover contract -------
    from dlrover_tpu.serving.chaos import FaultInjector
    from dlrover_tpu.serving.replica import (
        InferenceReplica,
        ReplicaPool,
    )

    def _chaos_pass(fi, engine_kw=None):
        """Drive the main mixed-length set through a 2-replica pool
        (direct pump loop, no threads: deterministic interleaving and
        the crash's evacuation runs synchronously inside the victim's
        own pump). Returns (requests, metrics, ttfts)."""
        cmetrics = ServingMetrics()
        cpool = ReplicaPool(metrics=cmetrics)
        creps = []
        for i in range(2):
            tag = f"replica-{i}"
            ceng = ContinuousBatcher(
                cfg, params, n_slots=n_slots, max_len=max_len,
                max_new_tokens=max_new, chunk=chunk, pad_id=-1,
                chaos=fi, chaos_tag=tag, **(engine_kw or {}),
            )
            csched = RequestScheduler(ceng, slo, metrics=cmetrics)
            crep = InferenceReplica(tag, csched, chaos=fi)
            cpool.add(crep)
            creps.append(crep)
        # compile warm-up per fresh engine, outside the timed region;
        # the injector is still quiescent here — the caller arms the
        # crash plan AFTER warm-up, relative to the step counter the
        # warm drain advanced
        for crep in creps:
            w = crep.scheduler.submit(prompts[0], max_new=2)
            crep.scheduler.run_to_completion()
            assert w.state.value == "done"
        return cpool, creps, cmetrics

    def _drain(creps):
        for _ in range(100_000):
            busy = False
            for crep in creps:
                busy = crep.scheduler.pump() or busy
            if not busy:
                return
        raise AssertionError("chaos pool did not drain")

    def _run_pool(fi, arm=None, engine_kw=None):
        cpool, creps, cmetrics = _chaos_pass(fi, engine_kw)
        if arm is not None:
            arm(fi, creps)
        reqs = [
            creps[i % 2].scheduler.submit(p, max_new=max_new)
            for i, p in enumerate(prompts)
        ]
        _drain(creps)
        cttfts = sorted(
            (r.first_token_ts - r.submit_ts) * 1000.0
            for r in reqs
            if r.first_token_ts is not None
        )
        return reqs, cmetrics, cttfts

    steady_reqs, _, steady_ttfts = _run_pool(
        FaultInjector(seed=0), engine_kw={"async_depth": 0}
    )

    def _arm(fi, creps):
        # warm-up advanced each engine's step counter; aim the crash
        # a few decode steps past wherever replica-0 is NOW so it
        # lands mid-drain with work both running and queued
        fi.crash_replica(
            "replica-0",
            at_step=creps[0].scheduler.engine._step_no + 3,
        )

    # the chaos pass runs at async_depth=1 against the depth-0 steady
    # pass: the parity check below then proves crash-evacuate-resume
    # stays byte-exact ACROSS pipelining depths, not just within one
    chaos_fi = FaultInjector(seed=0)
    chaos_reqs, chaos_metrics, chaos_ttfts = _run_pool(
        chaos_fi, arm=_arm, engine_kw={"async_depth": 1}
    )
    assert chaos_fi.fired, "chaos plan never fired"
    n_chaos_done = sum(
        1 for r in chaos_reqs if r.state.value == "done"
    )
    chaos_success_rate = n_chaos_done / len(chaos_reqs)
    chaos_parity_ok = [list(r.tokens) for r in chaos_reqs] == [
        list(r.tokens) for r in steady_reqs
    ]

    # ---- paged phase: paged KV layout vs the dense bank -----------------
    # (a) overhead: same mixed-length workload, scheduler-driven, once
    # per layout with IDENTICAL passes — a full-set warm drain first
    # (every prompt bucket's admission program, the chunk program, and
    # the paged table/publish programs all compile outside the timed
    # region; the paged layout has MORE admission-side programs than
    # the dense bank, so a one-request warm-up would bill its extra
    # compiles to TPOT and measure XLA, not paging). Passes INTERLEAVE
    # the layouts (dense, paged, dense, ...) and each side keeps the
    # best of its repetitions: a single pass's p50 wobbles ~10% under
    # CPU scheduler noise, and back-to-back same-layout passes would
    # fold machine drift between the two phases into the ratio. The
    # lock is steady-state paging overhead (gather + table
    # bookkeeping) under 10%.
    # longer decode runs than the main phase: TPOT here is the
    # STEADY-STATE decode claim, so the measured intervals should be
    # chunk-scan dominated — with short runs every interval absorbs a
    # neighbour slot's admission and the ratio measures admission
    # churn instead of the paging overhead it locks
    lp_new = min(3 * max_new, max_len - max(len(p) for p in prompts))
    # wider chunks than the latency-tuned main phase: TPOT here is
    # decode-bound by design, and the per-dispatch fixed cost (jit
    # call + the paged gather/scatter) should amortize the same way
    # it does in a throughput deployment. Both layouts use the same
    # chunk, so the comparison stays apples-to-apples.
    lp_chunk = 2 * chunk
    lp_slo = SloConfig(
        max_queue_depth=n_requests + 1,
        max_new_tokens=lp_new,
        default_deadline_s=600.0,
    )

    def _layout_pass(**layout_kw):
        eng = ContinuousBatcher(
            cfg, params, n_slots=n_slots, max_len=max_len,
            max_new_tokens=lp_new, chunk=lp_chunk, pad_id=-1,
            **layout_kw,
        )
        warm = RequestScheduler(eng, lp_slo, metrics=ServingMetrics())
        for p in prompts:
            warm.submit(p, max_new=lp_new)
        warm.run_to_completion()
        timed = RequestScheduler(eng, lp_slo, metrics=ServingMetrics())
        preqs = [timed.submit(p, max_new=lp_new) for p in prompts]
        timed.run_to_completion()
        ptpots = sorted(
            (r.finish_ts - r.first_token_ts)
            * 1000.0
            / (len(r.tokens) - 1)
            for r in preqs
            if r.first_token_ts is not None and len(r.tokens) > 1
        )
        return pct(ptpots, 0.5), eng

    _dense_p50s, _paged_p50s = [], []
    for i in range(8):
        # ABBA order: alternating which layout goes first each cycle
        # keeps any periodic background load from aliasing onto one
        # layout (strict A-B alternation can sample a ~pass-period
        # disturbance at exactly the paged slots, run after run)
        if i % 2 == 0:
            _dense_p50s.append(_layout_pass()[0])
            _paged_p50s.append(_layout_pass(kv_layout="paged")[0])
        else:
            _paged_p50s.append(_layout_pass(kv_layout="paged")[0])
            _dense_p50s.append(_layout_pass()[0])
    paged_dense_tpot_p50 = min(_dense_p50s)
    paged_tpot_p50 = min(_paged_p50s)
    # the LOCK ratio is PAIRED: each ABBA cycle compares the two
    # layouts back-to-back under the same machine conditions, and the
    # median over cycles drops outlier pairs. A ratio of independent
    # minima is NOT drift-proof — a single lucky dense pass (or an
    # unlucky paged one) minutes apart skews it, which on a shared
    # CPU box turns a real ~4% overhead into a 10%+ coin flip.
    _pair_ratios = sorted(
        pr / dr for dr, pr in zip(_dense_p50s, _paged_p50s)
    )
    _n = len(_pair_ratios)
    paged_pair_ratio = (
        _pair_ratios[_n // 2]
        if _n % 2
        else 0.5 * (_pair_ratios[_n // 2 - 1] + _pair_ratios[_n // 2])
    )

    # (b) oversubscription: drain the same set on a pool roughly half
    # the dense-equivalent footprint (raw engine, no scheduler gate —
    # the point is the engine's own preempt-and-swap). Correctness
    # lock: byte parity with the dense bank, zero requests lost.
    dense_eng = ContinuousBatcher(
        cfg, params, n_slots=n_slots, max_len=max_len,
        max_new_tokens=max_new, chunk=chunk, pad_id=-1,
    )
    dense_out = [o.tolist() for o in dense_eng.generate_all(prompts)]
    per_slot = (
        ContinuousBatcher(
            cfg, params, n_slots=n_slots, max_len=max_len,
            max_new_tokens=max_new, chunk=chunk, pad_id=-1,
            kv_layout="paged",
        )._pages_per_slot
    )
    # small enough that the live working set cannot fit (the smoke's
    # short requests round to far fewer pages than per_slot, so a
    # half-size pool would not actually pressure anything)
    oversub_pages = max(per_slot + 2, n_slots * per_slot // 4 + 1)
    oversub_eng = ContinuousBatcher(
        cfg, params, n_slots=n_slots, max_len=max_len,
        max_new_tokens=max_new, chunk=chunk, pad_id=-1,
        kv_layout="paged", n_pages=oversub_pages,
    )
    oversub_out = [
        o.tolist() for o in oversub_eng.generate_all(prompts)
    ]
    paged_parity_ok = oversub_out == dense_out
    paged_success_rate = sum(
        1 for o in oversub_out if len(o) > 0
    ) / len(prompts)
    oversub_stats = oversub_eng.paged_stats()

    # (c) copy-free sharing: warm the shared-system-prompt set on a
    # paged+prefix engine. Publishing the bare system prompt first
    # pins the shared page run; every tailed admission then warm-hits
    # it as a SUFFIX hit — pages shared by refcount, zero CoW.
    share_eng = ContinuousBatcher(
        pcfg, pparams, n_slots=p_slots, max_len=p_max_len,
        max_new_tokens=p_max_new, chunk=p_chunk, pad_id=-1,
        prefix_cache_rows=8, kv_layout="paged",
    )
    share_eng.generate_all([sys_prompt])  # publish the prefix run
    cow_before = share_eng.allocator.cow_copies
    share_eng.generate_all(shared_prompts)
    paged_warm_cow = share_eng.allocator.cow_copies - cow_before
    share_stats = share_eng.paged_stats()
    paged_hit_rate = share_eng.prefix_cache.stats()["hit_rate"]

    # ---- phase 7: tensor-parallel mesh slice (tp=1 vs tp=2) -----------
    # A replica as a named mesh slice: mesh_spec=2 shards params and the
    # KV bank along the head axis and lets GSPMD insert the collectives.
    # Parity is the whole contract — tp=2 must be byte-identical to the
    # dense tp=1 outputs already computed above (dense_out), because
    # head-sharding only splits matmul OUTPUT columns and replicates the
    # attention output before the out projection: same arithmetic,
    # chunked by head. Degrades to tp=1-only when the host has a single
    # device (real-TPU single-chip runs).
    mesh_devices = jax.local_device_count()
    _mesh_kv = cfg.n_kv_heads or cfg.n_heads
    mesh_tp = 2 if (mesh_devices >= 2 and _mesh_kv % 2 == 0) else 1
    mesh_tp1_tpot_p50 = paged_dense_tpot_p50
    mesh_tp2_tpot_p50 = 0.0
    mesh_parity_ok = True
    n_mesh_requests = 0
    if mesh_tp > 1:
        tp2_eng = ContinuousBatcher(
            cfg, params, n_slots=n_slots, max_len=max_len,
            max_new_tokens=max_new, chunk=chunk, pad_id=-1,
            mesh_spec=mesh_tp,
        )
        tp2_out = [o.tolist() for o in tp2_eng.generate_all(prompts)]
        mesh_parity_ok = tp2_out == dense_out
        n_mesh_requests = len(tp2_out)
        # TPOT through the same harness as the paged phase so the tp=1
        # side can reuse the dense minima measured there; two passes
        # and take the min (the first pays jit warmup noise)
        mesh_tp2_tpot_p50 = min(
            _layout_pass(mesh_spec=mesh_tp)[0] for _ in range(2)
        )
    # exposition: a mesh-aware scheduler pump publishes the slice shape
    # through ServingMetrics; the per-replica chip gauge is what the
    # chip-denominated autoscaler path is fed from
    mesh_eng = ContinuousBatcher(
        cfg, params, n_slots=n_slots, max_len=max_len,
        max_new_tokens=max_new, chunk=chunk, pad_id=-1,
        mesh_spec=mesh_tp,
    )
    mesh_metrics = ServingMetrics()
    mesh_sched = RequestScheduler(
        mesh_eng, lp_slo, metrics=mesh_metrics
    )
    mesh_sched.submit(prompts[0], max_new=2)
    mesh_sched.run_to_completion()
    _mesh_render = mesh_metrics.render()
    mesh_metrics_ok = (
        f"serving_mesh_tp {mesh_tp}" in _mesh_render
        and f"serving_replica_chips {mesh_tp}" in _mesh_render
    )

    # ---- phase 8: fused-kernel dispatch (shard_mapped Pallas path) ----
    # Which attention body the tp-sharded paged decode step actually
    # runs — asserted, not assumed. On a real TPU an 'auto' paged
    # replica must report kernel_path == "kernel" (the shard_mapped
    # Pallas paged-attention over the tp axis); on the CPU smoke 'auto'
    # must stay "reference" (no silent interpret-mode kernels in the
    # perf numbers). The paired cycle then runs the same engine shape
    # with only the attention body swapped: the kernel side rides
    # DLROVER_TPU_FORCE_KERNELS interpret mode on CPU (the ratio there
    # documents dispatch + token parity, not speed — interpret Pallas
    # is pure overhead), and is the fused-vs-XLA latency evidence on
    # TPU. attn_impl="reference" pins the XLA oracle on both backends.
    import dataclasses as _dc

    if on_tpu:
        kcfg, kparams = cfg, params
    else:
        # the smoke tiny cfg's head_dim=16 fails the kernel shape gate
        # (>=32); dim=128 over 4 heads is the narrowest passing width
        kcfg = _dc.replace(
            llama.LlamaConfig.tiny(dim=128, attn_impl="auto"),
            dtype=jnp.float32,
        )
        kparams = llama.init_params(kcfg, jax.random.PRNGKey(0))
    k_max_new = 8
    k_prompts = [
        rng.integers(1, 250, size=int(n)).tolist() for n in (5, 9, 12)
    ]

    def _kernel_engine(c):
        return ContinuousBatcher(
            c, kparams, n_slots=2, max_len=64,
            max_new_tokens=k_max_new, chunk=4, pad_id=-1,
            kv_layout="paged", mesh_spec=mesh_tp,
        )

    k_auto = _kernel_engine(kcfg)
    kernel_path = k_auto.kernel_path
    kernel_path_ok = kernel_path == (
        "kernel" if on_tpu else "reference"
    )
    # exposition: a scheduler pump must publish the dispatched path
    # through the serving_kernel_path_steps_total counter family
    k_metrics = ServingMetrics()
    k_slo = SloConfig(
        max_queue_depth=len(k_prompts) + 1,
        max_new_tokens=k_max_new,
        default_deadline_s=600.0,
    )
    k_sched = RequestScheduler(k_auto, k_slo, metrics=k_metrics)
    for p in k_prompts:
        k_sched.submit(p, max_new=k_max_new)
    k_sched.run_to_completion()
    kernel_metrics_ok = (
        f'serving_kernel_path_steps_total{{path="{kernel_path}"}}'
        in k_metrics.render()
        and k_metrics.kernel_path_steps.get(kernel_path, 0) > 0
    )

    def _kernel_pass(body):
        # body="kernel" takes the shard_mapped Pallas path (forced
        # interpret kernels off-TPU); "reference" pins the XLA oracle
        c = (
            kcfg
            if body == "kernel"
            else _dc.replace(kcfg, attn_impl="reference")
        )
        prev = os.environ.get("DLROVER_TPU_FORCE_KERNELS")
        if body == "kernel" and not on_tpu:
            os.environ["DLROVER_TPU_FORCE_KERNELS"] = "1"
        try:
            eng = _kernel_engine(c)
            eng.generate_all(k_prompts)  # warm: pays the compiles
            t0 = time.monotonic()
            out = [o.tolist() for o in eng.generate_all(k_prompts)]
            dt = time.monotonic() - t0
        finally:
            if prev is None:
                os.environ.pop("DLROVER_TPU_FORCE_KERNELS", None)
            else:
                os.environ["DLROVER_TPU_FORCE_KERNELS"] = prev
        ntok = sum(len(o) for o in out)
        return out, dt * 1000.0 / max(ntok, 1), eng.kernel_path

    kern_out, kernel_tpot_ms, _kpath = _kernel_pass("kernel")
    ref_out, kernel_ref_tpot_ms, _rpath = _kernel_pass("reference")
    kernel_forced_path_ok = (
        _kpath == "kernel" and _rpath == "reference"
    )
    kernel_parity_ok = kern_out == ref_out
    # recorded, never locked <1: only the TPU run is a speed claim
    kernel_tpot_ratio = kernel_tpot_ms / max(kernel_ref_tpot_ms, 1e-9)

    # ---- phase 9: disaggregated prefill/decode (MPMD phase split) -----
    # A mixed long-prefill/short-decode workload on (a) one colocated
    # replica — every long admission runs its prefill INSIDE the same
    # engine that is decoding the shorts, stalling their token cadence
    # — and (b) a prefill-role + decode-role pair on separate devices:
    # the prefill replica absorbs the long prompts while the decode
    # replica, which only pays the copy-free page-run adoption (a
    # scatter, not a forward pass), keeps stepping. The lock is decode
    # TPOT p99 over the SHORT requests: disaggregated must beat
    # colocated by a margin. Correctness rides along: greedy byte
    # parity between the two topologies, success 1.0 including a
    # deterministic pass with one injected mid-handoff crash (the
    # resume-by-replay fallback), and zero leaked pages after drain.
    # Uses a dedicated model sized so per-step decode compute is tiny
    # while a single long prefill costs hundreds of decode steps — the
    # phase's signal IS prefill cost, and both the shared pcfg and the
    # main cfg's smoke prompts are too cheap to stall anything
    # measurable relative to their own decode step.
    if on_tpu:
        dcfg = cfg
        d_max_len = min(int(cfg.max_seq_len), 2048)
        d_slots, d_chunk, d_short_new, d_long_new = 8, 4, 64, 1
        d_short_lo, d_short_hi = 8, 16
        d_long_lo, d_long_hi = (
            int(0.75 * d_max_len), int(0.92 * d_max_len)
        )
        n_d_short, n_d_long = 6, 4
    else:
        import dataclasses

        dcfg = dataclasses.replace(
            llama.LlamaConfig.tiny(), dtype=jnp.float32,
            max_seq_len=2048,
        )
        d_max_len = 2048
        d_slots, d_chunk, d_short_new, d_long_new = 6, 1, 16, 1
        d_short_lo, d_short_hi = 4, 10
        d_long_lo, d_long_hi = 1600, 1900
        n_d_short, n_d_long = 4, 4
    dparams = llama.init_params(dcfg, jax.random.PRNGKey(1))
    drng = np.random.default_rng(7)
    d_short_prompts = [
        drng.integers(
            1, min(500, dcfg.vocab_size), size=int(n)
        ).tolist()
        for n in drng.integers(d_short_lo, d_short_hi, size=n_d_short)
    ]
    d_long_prompts = [
        drng.integers(
            1, min(500, dcfg.vocab_size), size=int(n)
        ).tolist()
        for n in drng.integers(d_long_lo, d_long_hi, size=n_d_long)
    ]
    d_slo = SloConfig(
        max_queue_depth=n_d_short + n_d_long + 4,
        max_new_tokens=max(d_short_new, d_long_new),
        default_deadline_s=600.0,
    )
    d_devs = jax.local_devices()

    def _drain_pool(scheds):
        for _ in range(200_000):
            busy = False
            for s in scheds:
                busy = s.pump() or busy
            if not busy:
                return
        raise AssertionError("disagg pool did not drain")

    def _disagg_build(disagg, fi=None):
        dmetrics = ServingMetrics()
        dpool = ReplicaPool(metrics=dmetrics)
        roles = (
            [
                ("prefill", d_devs[0]),
                ("decode", d_devs[min(1, len(d_devs) - 1)]),
            ]
            if disagg
            else [("colocated", d_devs[0])]
        )
        scheds = []
        for role, dev in roles:
            # each engine committed to its own (virtual) device so the
            # prefill forward and the decode chunk scan can genuinely
            # overlap; the device handoff transport device_puts the
            # shipped run across at adoption
            with jax.default_device(dev):
                prm = jax.device_put(dparams, dev)
                eng = ContinuousBatcher(
                    dcfg, prm, n_slots=d_slots, max_len=d_max_len,
                    max_new_tokens=max(d_short_new, d_long_new),
                    chunk=d_chunk, pad_id=-1, kv_layout="paged",
                    replica_role=role,
                )
            sch = RequestScheduler(eng, d_slo, metrics=dmetrics)
            dpool.add(InferenceReplica(role, sch))
            scheds.append(sch)
        if fi is not None:
            dpool.handoff.chaos = fi
            dpool.handoff.chaos_tag = "handoff"
        # warm the full path outside the timed region: short + long
        # prefill buckets, the chunk scan, and (disagg) the handoff
        # gather/scatter + adoption programs
        for p, mn in (
            (d_short_prompts[0], 2),
            (d_long_prompts[0], 2),
        ):
            dpool.submit(p, max_new=mn)
            _drain_pool(scheds)
        return dpool, scheds, dmetrics

    def _pump_loop(sched, stop):
        while not stop.is_set():
            try:
                busy = sched.pump()
            except Exception:  # noqa: BLE001 — states carry the story
                break
            if not busy:
                time.sleep(0.0005)

    def _disagg_perf(disagg):
        dpool, scheds, dmetrics = _disagg_build(disagg)
        stop = threading.Event()
        threads = [
            threading.Thread(
                target=_pump_loop, args=(s, stop), daemon=True
            )
            for s in scheds
        ]
        for t in threads:
            t.start()
        sreqs = [
            dpool.submit(p, max_new=d_short_new)
            for p in d_short_prompts
        ]
        # longs land once every short is mid-decode, so their prefills
        # contend with the shorts' token cadence by construction
        t_dead = time.monotonic() + 120.0
        while time.monotonic() < t_dead and any(
            r.first_token_ts is None for r in sreqs
        ):
            time.sleep(0.001)
        lreqs = [
            dpool.submit(p, max_new=d_long_new)
            for p in d_long_prompts
        ]
        for r in sreqs + lreqs:
            r.wait(timeout=300.0)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        dtpots = sorted(
            (r.finish_ts - r.first_token_ts)
            * 1000.0
            / (len(r.tokens) - 1)
            for r in sreqs
            if r.first_token_ts is not None and len(r.tokens) > 1
        )
        outs = [list(r.tokens) for r in sreqs + lreqs]
        done = sum(
            1
            for r in sreqs + lreqs
            if r.state.value == "done"
        )
        return pct(dtpots, 0.99), outs, done, dmetrics, scheds

    coloc_runs = [_disagg_perf(False) for _ in range(2)]
    disagg_runs = [_disagg_perf(True) for _ in range(2)]
    disagg_coloc_p99 = min(r[0] for r in coloc_runs)
    disagg_p99 = min(r[0] for r in disagg_runs)
    n_disagg_total = n_d_short + n_d_long
    disagg_parity_ok = all(
        r[1] == coloc_runs[0][1] for r in coloc_runs + disagg_runs
    )
    disagg_success_rate = min(
        r[2] / n_disagg_total for r in disagg_runs
    )
    disagg_handoffs = sum(
        disagg_runs[-1][3].handoff_total.values()
    )
    disagg_pages_adopted = int(
        disagg_runs[-1][4][1].engine.allocator.pages_adopted
    )

    # crash pass, deterministic pump (no threads): one transient
    # injected failure on the first post-warm-up handoff — the
    # package is lost mid-flight and the scheduler must fall back to
    # resume-by-replay, losing zero requests and zero pages
    disagg_fi = FaultInjector(seed=0)
    cpool, cscheds, _ = _disagg_build(True, fi=disagg_fi)
    disagg_fi.fail_engine_step(
        "handoff", at_step=cpool.handoff._step
    )
    dcreqs = [
        cpool.submit(p, max_new=d_short_new)
        for p in d_short_prompts
    ] + [
        cpool.submit(p, max_new=d_long_new)
        for p in d_long_prompts
    ]
    _drain_pool(cscheds)
    assert disagg_fi.fired, "mid-handoff crash never fired"
    disagg_crash_success = sum(
        1 for r in dcreqs if r.state.value == "done"
    ) / len(dcreqs)
    disagg_crash_leaked = 0
    for s in cscheds:
        s.engine.allocator.check()  # refcount/free-list consistency
        disagg_crash_leaked += int(s.engine.allocator.used_pages)

    # ---- phase 10: elastic resize + drain-free weight refresh ---------
    # Chip loss mid-workload on a tensor-parallel replica: the
    # scheduler catches ChipLost inside its own pump and re-forms the
    # mesh live at the largest surviving tp (serving/elastic.py) —
    # every in-flight request is preempted and replayed instead of
    # failing over or crashing the replica. The lock is success 1.0
    # AND greedy byte parity with a no-fault oracle at the original
    # tp. The reverse direction rides along: a weight refresh staged
    # mid-drain must fence every request to a single weight version
    # (no mixed-version step, ever) and commit at the next idle
    # boundary. tp scales to the host: 4 when the device count and
    # KV-head divisibility allow (half the slice dies, tp4 -> tp2),
    # else the mesh phase's tp (tp2 -> tp1 on the CPU smoke).
    elastic_tp = (
        4 if (mesh_devices >= 4 and _mesh_kv % 4 == 0) else mesh_tp
    )
    elastic_chunk = 2  # several steps per drain: the fault must land
    # mid-decode, not after a single chunk finished everything
    elastic_success_rate = 1.0
    elastic_parity_ok = True
    elastic_resized_tp = elastic_tp
    elastic_replayed = 0
    elastic_downtime_ms = 0.0
    elastic_metrics_ok = True
    n_elastic_requests = 0
    if elastic_tp > 1:
        el_oracle = ContinuousBatcher(
            cfg, params, n_slots=n_slots, max_len=max_len,
            max_new_tokens=max_new, chunk=elastic_chunk, pad_id=-1,
            mesh_spec=elastic_tp,
        )
        el_want = [
            o.tolist() for o in el_oracle.generate_all(prompts)
        ]
        el_fi = FaultInjector(seed=0)
        el_metrics = ServingMetrics()
        el_eng = ContinuousBatcher(
            cfg, params, n_slots=n_slots, max_len=max_len,
            max_new_tokens=max_new, chunk=elastic_chunk, pad_id=-1,
            mesh_spec=elastic_tp, chaos=el_fi, chaos_tag="elastic",
        )
        el_sched = RequestScheduler(el_eng, slo, metrics=el_metrics)
        # warm outside the measured drain, then aim the loss a few
        # steps past the current counter so it lands mid-decode with
        # work both running and queued
        el_w = el_sched.submit(prompts[0], max_new=2)
        el_sched.run_to_completion()
        assert el_w.state.value == "done"
        el_fi.lose_chip(
            "elastic", elastic_tp // 2,
            at_step=el_eng._step_no + 3,
        )
        el_reqs = [
            el_sched.submit(p, max_new=max_new) for p in prompts
        ]
        el_sched.run_to_completion()
        assert el_fi.fired, "elastic chip-loss plan never fired"
        n_elastic_requests = len(el_reqs)
        elastic_success_rate = sum(
            1 for r in el_reqs if r.state.value == "done"
        ) / len(el_reqs)
        elastic_parity_ok = [
            list(r.tokens) for r in el_reqs
        ] == el_want
        elastic_resized_tp = el_eng.mesh_tp
        el_stats = el_eng.elastic_stats()
        elastic_replayed = int(el_stats["replayed_requests"])
        elastic_downtime_ms = el_stats["resize_downtime_ms"]
        _el_render = el_metrics.render()
        elastic_metrics_ok = (
            'serving_resize_total{direction="shrink"} 1'
            in _el_render
            and f"serving_mesh_tp {el_eng.mesh_tp}" in _el_render
        )

    # drain-free refresh, engine-driven for determinism: fresh leaves
    # with identical values, so the lock is the version fence itself,
    # not the arithmetic — request 0 drains entirely on version 0
    # while the swap stays staged, request 1 crosses the submit fence
    # and runs entirely on version 1
    er_eng = ContinuousBatcher(
        cfg, params, n_slots=n_slots, max_len=max_len,
        max_new_tokens=max_new, chunk=elastic_chunk, pad_id=-1,
        mesh_spec=elastic_tp,
    )
    er_fresh = jax.tree_util.tree_map(lambda x: x + 0, params)
    er_i0 = er_eng.submit(prompts[0])
    er_eng.step()                      # mid-drain
    er_eng.update_params(er_fresh)     # defer mode: stages
    er_staged_ok = er_eng.weight_version == 0
    while er_eng.has_work():
        er_eng.step()
    er_i1 = er_eng.submit(prompts[1])  # fence: the swap commits here
    er_committed_ok = er_eng.weight_version == 1
    while er_eng.has_work():
        er_eng.step()
    elastic_refresh_ok = (
        er_staged_ok
        and er_committed_ok
        and er_eng._requests[er_i0].versions == {0}
        and er_eng._requests[er_i1].versions == {1}
    )

    # ---- phase 11: multi-adapter LoRA serving (batched tenant mix) ----
    # Many fine-tunes behind one replica: requests tagged with an
    # adapter_id decode through ONE base-model forward, each batch row
    # gathering its own low-rank delta from the stacked device bank
    # (serving/adapters.py). The workload oversubscribes the bank on
    # purpose — more registered tenants than device cache slots — so
    # the LRU residency path (hits, uploads, pinned-aware evictions)
    # is exercised, not just the happy path. Locks: the mixed-tenant
    # TPOT p50 stays within 25% of the single-model baseline (the
    # BGMV gather is rank-thin — per-tenant replicas are the
    # alternative being priced), every request is byte-identical to a
    # dedicated merged-weight engine for its adapter, and the device
    # cache shows real reuse (hit rate > 0) under oversubscription.
    from dlrover_tpu.models import lora as lora_mod
    from dlrover_tpu.serving.adapters import AdapterRegistry

    n_adapters, adapter_cache_slots = 4, 2
    areg = AdapterRegistry(cfg, max_rank=8)
    amerged = {None: params}
    for i in range(n_adapters):
        alc = lora_mod.LoraConfig(rank=4, alpha=8.0)
        alcfg, ap = lora_mod.inject(
            cfg, params, alc, jax.random.PRNGKey(50 + i)
        )
        alay = dict(ap["layers"])
        for k in list(alay):
            # inject zeroes B (delta starts at 0); randomize it so
            # each tenant's delta is live and tenant-distinct
            if k.endswith(lora_mod.LORA_B):
                alay[k] = (
                    jax.random.normal(
                        jax.random.PRNGKey(150 + i),
                        alay[k].shape,
                        jnp.float32,
                    )
                    * 0.05
                )
        ap = dict(ap)
        ap["layers"] = alay
        areg.register(
            f"tenant-{i}", lora_mod.adapter_state_dict(ap), alpha=8.0
        )
        amerged[f"tenant-{i}"] = lora_mod.merge(alcfg, ap)
    # 1-in-5 base traffic, the rest round-robin over the tenants —
    # every drain mixes slot-0 rows with all four adapters
    adapter_ids = [
        None if i % 5 == 0 else f"tenant-{i % 5 - 1}"
        for i in range(n_requests)
    ]

    def _adapter_pass(with_adapters):
        akw = (
            {
                "adapter_registry": areg,
                "adapter_cache_slots": adapter_cache_slots,
            }
            if with_adapters
            else {}
        )
        aids = (
            adapter_ids if with_adapters else [None] * n_requests
        )
        eng = ContinuousBatcher(
            cfg, params, n_slots=n_slots, max_len=max_len,
            max_new_tokens=lp_new, chunk=lp_chunk, pad_id=-1, **akw,
        )
        warm = RequestScheduler(eng, lp_slo, metrics=ServingMetrics())
        for p, aid in zip(prompts, aids):
            warm.submit(p, max_new=lp_new, adapter_id=aid)
        warm.run_to_completion()
        timed = RequestScheduler(
            eng, lp_slo, metrics=ServingMetrics()
        )
        areqs = [
            timed.submit(p, max_new=lp_new, adapter_id=aid)
            for p, aid in zip(prompts, aids)
        ]
        timed.run_to_completion()
        atpots = sorted(
            (r.finish_ts - r.first_token_ts)
            * 1000.0
            / (len(r.tokens) - 1)
            for r in areqs
            if r.first_token_ts is not None and len(r.tokens) > 1
        )
        return pct(atpots, 0.5), eng

    # ABBA pairing + paired-median ratio, same discipline (and same
    # rationale) as the paged phase's lock
    _single_p50s, _amix_p50s = [], []
    _amix_eng = None
    for i in range(4):
        if i % 2 == 0:
            _single_p50s.append(_adapter_pass(False)[0])
            p50, _amix_eng = _adapter_pass(True)
            _amix_p50s.append(p50)
        else:
            p50, _amix_eng = _adapter_pass(True)
            _amix_p50s.append(p50)
            _single_p50s.append(_adapter_pass(False)[0])
    adapter_single_tpot_p50 = min(_single_p50s)
    adapter_mix_tpot_p50 = min(_amix_p50s)
    _a_ratios = sorted(
        ar / sr for sr, ar in zip(_single_p50s, _amix_p50s)
    )
    _an = len(_a_ratios)
    adapter_pair_ratio = (
        _a_ratios[_an // 2]
        if _an % 2
        else 0.5 * (_a_ratios[_an // 2 - 1] + _a_ratios[_an // 2])
    )
    a_stats = _amix_eng.adapter_stats()
    adapter_hit_rate = a_stats["hits"] / max(
        a_stats["hits"] + a_stats["misses"], 1.0
    )

    # byte parity: the mixed batch vs one dedicated merged-weight
    # engine per tenant (base rows vs the plain-params engine) —
    # greedy, raw engine, so the comparison is exact. The bank is
    # sized to the tenant count here: the raw engine pins every
    # submitted request's slot up front (no scheduler to absorb
    # AdapterCacheFull backpressure), and oversubscription is the
    # TIMED phase's subject, not parity's
    apar_eng = ContinuousBatcher(
        cfg, params, n_slots=n_slots, max_len=max_len,
        max_new_tokens=max_new, chunk=chunk, pad_id=-1,
        adapter_registry=areg,
        adapter_cache_slots=n_adapters,
    )
    for p, aid in zip(prompts, adapter_ids):
        apar_eng.submit(p, adapter_id=aid)
    amix_out = [o.tolist() for o in apar_eng.generate_all([])]
    adapter_parity_ok = True
    for aid in amerged:
        rows = [
            i for i, a in enumerate(adapter_ids) if a == aid
        ]
        if not rows:
            continue
        oracle_eng = ContinuousBatcher(
            cfg, amerged[aid], n_slots=n_slots, max_len=max_len,
            max_new_tokens=max_new, chunk=chunk, pad_id=-1,
        )
        want = [
            o.tolist()
            for o in oracle_eng.generate_all(
                [prompts[i] for i in rows]
            )
        ]
        if [amix_out[i] for i in rows] != want:
            adapter_parity_ok = False

    # ---- phase 12: fleet front door (affinity routing + forecast) -----
    # Three replicas behind ONE pool.submit front door, a multi-tenant
    # shared-system-prompt workload (each tenant = its own system
    # prompt, every request that tenant's prompt + a short tail).
    # Routing is the only variable: the SAME rotated submission order
    # runs once with prefix-affinity routing ON and once OFF (pure
    # least-loaded), plus once through a single unrouted engine — the
    # hit-rate ceiling AND the byte oracle. The rotation is
    # adversarial for load-only routing on purpose: position k of
    # every round drains to replica k (ties re-rank from insertion
    # order), so tenants sweep the fleet and re-prefill their system
    # prompt on every replica, while affinity pins each tenant to the
    # replica already advertising its prefix. Locks: fleet hit rate
    # within noise of the single-replica ceiling and strictly above
    # least-loaded, the warm-TTFT tail (p90) and mean strictly below
    # least-loaded, and byte parity across all three passes (routing
    # changes WHERE a request runs, never WHAT it emits). The
    # forecast leg replays a seeded diurnal pressure trace through
    # predictive_scale: the advisor must receive a chip-denominated
    # scale-up BEFORE the trace's pressure peak.
    fleet_replicas, fleet_tenants, fleet_rounds = 3, 3, 6
    frng = np.random.default_rng(12)  # phase-local workload rng
    f_sys = [
        frng.integers(
            1, min(500, pcfg.vocab_size), size=sys_len
        ).tolist()
        for _ in range(fleet_tenants)
    ]
    # tails SHORTER than the digest block (the radix cache's 16): the
    # block-aligned published prefix of every request is then exactly
    # the tenant's system prompt, so all of a tenant's requests share
    # one advertised digest (a tail at/over the block would publish
    # per-request digests nothing ever re-matches)
    f_prompts = [
        s
        + frng.integers(
            1, min(500, pcfg.vocab_size), size=8
        ).tolist()
        for s in f_sys
    ]
    f_warm_sys = frng.integers(
        1, min(500, pcfg.vocab_size), size=sys_len
    ).tolist()
    f_slo = SloConfig(
        max_queue_depth=fleet_tenants * fleet_rounds + 2,
        max_new_tokens=p_max_new,
        default_deadline_s=600.0,
    )

    def _fleet_warm(fsched):
        # same two-step warm-up as the prefix phase — bare system
        # prompt (cold-path compile, publishes depth exactly
        # sys_len), then a tailed request (warm-path compile) — on a
        # THROWAWAY prefix so the timed workload starts cold
        fsched.submit(f_warm_sys, max_new=p_max_new)
        fsched.run_to_completion()
        fsched.submit(
            f_warm_sys + f_prompts[0][-8:], max_new=p_max_new
        )
        fsched.run_to_completion()

    def _fleet_cache_totals(freps):
        th = tm = 0
        for frep in freps:
            st = frep.scheduler.engine.prefix_cache.stats()
            th += int(st["hits"])
            tm += int(st["misses"])
        return th, tm

    def _fleet_pass(affinity):
        """One routed pass: returns (rows, hit_rate, warm ttfts,
        pool, metrics) where rows = (tenant, round, request)."""
        fmetrics = ServingMetrics()
        fpool = ReplicaPool(
            metrics=fmetrics, affinity_routing=affinity
        )
        freps = []
        for i in range(fleet_replicas):
            feng = ContinuousBatcher(
                pcfg, pparams, n_slots=p_slots, max_len=p_max_len,
                max_new_tokens=p_max_new, chunk=p_chunk, pad_id=-1,
                prefix_cache_rows=8,
            )
            fsched = RequestScheduler(feng, f_slo, metrics=fmetrics)
            frep = InferenceReplica(f"fleet-{i}", fsched)
            fpool.add(frep)
            freps.append(frep)
        for frep in freps:
            _fleet_warm(frep.scheduler)
        fpool.check_replicas()
        base_h, base_m = _fleet_cache_totals(freps)
        rows = []
        for rnd in range(fleet_rounds):
            for pos in range(fleet_tenants):
                t = (pos + rnd) % fleet_tenants
                r = fpool.submit(f_prompts[t], max_new=p_max_new)
                rows.append((t, rnd, r))
                # heartbeat between arrivals: publishes fresh digests
                # and re-ranks on live load — what the background
                # pool loop does between requests
                fpool.check_replicas()
            _drain(freps)
            fpool.check_replicas()
        th, tm = _fleet_cache_totals(freps)
        lookups = (th - base_h) + (tm - base_m)
        hit_rate = (th - base_h) / max(lookups, 1)
        # round 0 is the cold sweep in BOTH passes; warm TTFT is
        # rounds >= 1, where only routing decides cold vs warm
        ttfts = sorted(
            (r.first_token_ts - r.submit_ts) * 1000.0
            for t, rnd, r in rows
            if rnd >= 1 and r.first_token_ts is not None
        )
        return rows, hit_rate, ttfts, fpool, fmetrics

    fleet_rows, fleet_hit_rate, fleet_ttfts, fleet_pool, _fm = (
        _fleet_pass(affinity=True)
    )
    lb_rows, fleet_lb_hit_rate, fleet_lb_ttfts, _lbp, _lbm = (
        _fleet_pass(affinity=False)
    )

    # single unrouted engine: the hit-rate ceiling (every request
    # lands where its prefix lives, by construction) and the byte
    # oracle the routed passes must match token-for-token
    s_eng = ContinuousBatcher(
        pcfg, pparams, n_slots=p_slots, max_len=p_max_len,
        max_new_tokens=p_max_new, chunk=p_chunk, pad_id=-1,
        prefix_cache_rows=8,
    )
    s_sched = RequestScheduler(
        s_eng, f_slo, metrics=ServingMetrics()
    )
    _fleet_warm(s_sched)
    s_st = s_eng.prefix_cache.stats()
    s_base_h, s_base_m = int(s_st["hits"]), int(s_st["misses"])
    single_tokens = {}
    for rnd in range(fleet_rounds):
        for pos in range(fleet_tenants):
            t = (pos + rnd) % fleet_tenants
            r = s_sched.submit(f_prompts[t], max_new=p_max_new)
            s_sched.run_to_completion()
            single_tokens.setdefault(t, list(r.tokens))
    s_st = s_eng.prefix_cache.stats()
    s_lookups = (int(s_st["hits"]) - s_base_h) + (
        int(s_st["misses"]) - s_base_m
    )
    fleet_single_hit_rate = (
        int(s_st["hits"]) - s_base_h
    ) / max(s_lookups, 1)
    fleet_parity_ok = all(
        list(r.tokens) == single_tokens[t]
        for t, _rnd, r in fleet_rows + lb_rows
    )

    # forecast leg: a seeded diurnal pressure trace (night flat,
    # morning ramp, midday peak, decline) replayed into the brain
    # store with EXPLICIT 10s-apart timestamps — the fitted slope
    # must come from the trace's clock, not the bench's wall clock —
    # and predictive_scale run after every sample. The lock is lead
    # time: the first chip-denominated up-hint reaches the advisor
    # strictly before the trace's pressure/queue peak.
    from dlrover_tpu.brain.datastore import (
        JobMetricsStore,
        RuntimeSample,
    )
    from dlrover_tpu.master.auto_scaler import ServingScaleAdvisor

    fadvisor = ServingScaleAdvisor(max_replicas=8)
    fleet_pool.advisor = fadvisor.on_hint
    # prove the live telemetry wiring once — real fleet stats (queue
    # depth, pressure, hit rate, chips) flow into a store
    fleet_pool.brain_store = JobMetricsStore()
    tele_sample = fleet_pool.publish_telemetry()
    forecast_telemetry_ok = (
        tele_sample is not None and tele_sample.role == "serving"
    )
    fstore = JobMetricsStore()
    fleet_pool.brain_store = fstore
    f_trace = []
    for i in range(30):
        if i < 8:
            pr = 0.30
        elif i <= 20:
            pr = min(1.0, 0.30 + 0.06 * (i - 8))
        else:
            pr = max(0.2, 1.0 - 0.08 * (i - 20))
        f_trace.append((10.0 * i, pr, int(round(pr * 20))))
    forecast_peak_idx = max(
        range(len(f_trace)), key=lambda i: f_trace[i][2]
    )
    forecast_first_up_idx = -1
    forecast_chip_delta = 0
    for i, (ts_s, pr, qd) in enumerate(f_trace):
        fstore.add_sample(
            RuntimeSample(
                job_uuid=fleet_pool.job_uuid,
                role="serving",
                num_nodes=fleet_replicas,
                cpu_percent=pr * 100.0,
                ts=ts_s,
                queue_depth=qd,
            )
        )
        f_hint = fleet_pool.predictive_scale()
        if (
            f_hint is not None
            and f_hint["direction"] == "up"
            and forecast_first_up_idx < 0
        ):
            forecast_first_up_idx = i
            forecast_chip_delta = (
                f_hint["chips"] - f_hint["current_chips"]
            )
    forecast_lead_samples = (
        forecast_peak_idx - forecast_first_up_idx
        if forecast_first_up_idx >= 0
        else -1
    )

    # ---- phase 13: priority tiers + preemption, trace-driven ----------
    # Two legs. (a) Preempt showcase: batch-tier work fills every slot
    # of a one-replica scheduler, then a latency-tier arrival lands —
    # admission preemption MUST fire (deterministically, not
    # trace-luck), and the evicted victim must finish byte-identical
    # to an undisturbed run (resume-by-replay). (b) Trace replay: a
    # seeded diurnal multi-turn workload (serving/workload.py) drives
    # a 3-replica pool three ways — the tiered mixed replay, a
    # latency-only solo replay (whole sessions, so prompt chains stay
    # intact: the interference-free TTFT baseline), and an untiered
    # oracle replay (the byte oracle: tier labels change WHEN a
    # request decodes, never WHAT it emits). Locks: >=1 preemption
    # with byte parity, mixed-vs-solo latency p99 TTFT within a
    # bounded multiple, success rate 1.0 (nothing shed, nothing
    # failed), and the trace's own arrival-count series pushed
    # through predictive_scale must produce a chip-denominated
    # up-hint BEFORE the arrival peak — the generator feeding the
    # PR 13 forecast loop end-to-end.
    from dlrover_tpu.serving.workload import (
        SessionBook,
        WorkloadConfig,
        generate_trace,
    )

    trng = np.random.default_rng(13)
    tp_prompts = [
        trng.integers(
            1, min(500, pcfg.vocab_size), size=n
        ).tolist()
        for n in (12, 9, 7)
    ]
    tp_oracle_eng = ContinuousBatcher(
        pcfg, pparams, n_slots=3, max_len=p_max_len,
        max_new_tokens=p_max_new, chunk=p_chunk, pad_id=-1,
    )
    tp_want = [
        list(map(int, o))
        for o in tp_oracle_eng.generate_all(tp_prompts)
    ]
    tp_metrics = ServingMetrics()
    tp_sched = RequestScheduler(
        ContinuousBatcher(
            pcfg, pparams, n_slots=2, max_len=p_max_len,
            max_new_tokens=p_max_new, chunk=p_chunk, pad_id=-1,
        ),
        SloConfig(
            max_queue_depth=8,
            max_new_tokens=p_max_new,
            default_deadline_s=600.0,
        ),
        metrics=tp_metrics,
    )
    tp_batch = [
        tp_sched.submit(
            p, max_new=p_max_new, deadline_s=600.0, tier="batch"
        )
        for p in tp_prompts[:2]
    ]
    tp_sched.pump()  # both batch requests now occupy the two slots
    tp_lat = tp_sched.submit(
        tp_prompts[2], max_new=p_max_new, deadline_s=600.0,
        tier="latency",
    )
    tp_sched.run_to_completion()
    tier_showcase_preemptions = tp_metrics.tier_preempted_total[
        "batch"
    ]
    tier_preempt_parity_ok = (
        tier_showcase_preemptions >= 1
        and sum(r.preemptions for r in tp_batch) >= 1
        and [r.tokens for r in tp_batch] == tp_want[:2]
        and tp_lat.tokens == tp_want[2]
        and all(r.state.value == "done" for r in tp_batch)
    )

    tier_cfg = WorkloadConfig(
        seed=13,
        horizon_s=40.0,
        base_rate=0.5,
        burst_amplitude=0.9,
        period_s=40.0,
        turns_lo=1,
        turns_hi=3,
        think_time_s=3.0,
        user_tokens_lo=4,
        user_tokens_hi=10,
        max_new_lo=4,
        max_new_hi=p_max_new,
        long_context_prob=0.1,
        long_context_tokens=64,
        system_prompt_tokens=8,
        vocab=min(500, pcfg.vocab_size),
        max_prompt_tokens=min(256, p_max_len - p_max_new - 1),
        latency_frac=0.5,
        batch_frac=0.25,
        # deadlines are NOT the phase's subject (wall-clock deadlines
        # on a CPU smoke would measure the host, not the policy):
        # generous bounds, and the success-rate lock proves nothing
        # shed anyway
        latency_deadline_s=600.0,
        standard_deadline_s=600.0,
        batch_deadline_s=600.0,
    )
    tier_trace = generate_trace(tier_cfg)
    tier_slo = SloConfig(
        max_queue_depth=len(tier_trace.events) + 4,
        max_new_tokens=p_max_new,
        default_deadline_s=600.0,
    )

    def _tier_replay(tiered, sessions=None):
        """Replay the trace through a 3-replica pool: submit every
        event whose session context is ready (SessionBook defers
        turn k+1 until turn k's reply lands — a chat client cannot
        type ahead of the stream), pump all replicas, fold replies
        back. `sessions` filters WHOLE sessions (latency-solo leg);
        `tiered=False` strips the labels (the untiered oracle).
        Returns ((session, turn) -> request, metrics, pool)."""
        rmetrics = ServingMetrics()
        rpool = ReplicaPool(metrics=rmetrics)
        rreps = []
        for i in range(3):
            rsched = RequestScheduler(
                ContinuousBatcher(
                    pcfg, pparams, n_slots=p_slots,
                    max_len=p_max_len, max_new_tokens=p_max_new,
                    chunk=p_chunk, pad_id=-1,
                ),
                tier_slo,
                metrics=rmetrics,
            )
            rrep = InferenceReplica(f"tier-{i}", rsched)
            rpool.add(rrep)
            rreps.append(rrep)
        book = SessionBook(tier_trace)
        todo = [
            ev
            for ev in tier_trace.events
            if sessions is None or ev.session in sessions
        ]
        live, out = {}, {}
        for _ in range(100_000):
            if not todo and not live:
                return out, rmetrics, rpool
            for ev in list(todo):
                if book.ready(ev):
                    r = rpool.submit(
                        book.prompt_for(ev).tolist(),
                        max_new=ev.max_new,
                        deadline_s=ev.deadline_s,
                        tier=ev.tier if tiered else None,
                    )
                    live[id(r)] = (ev, r)
                    out[(ev.session, ev.turn)] = r
                    todo.remove(ev)
            for rrep in rreps:
                rrep.scheduler.pump()
            for key, (ev, r) in list(live.items()):
                if r.state.value in ("done", "shed", "failed"):
                    if r.state.value == "done":
                        book.record_reply(ev, list(r.tokens))
                    else:
                        # a dead turn orphans the rest of its
                        # session's chain — drop those events
                        todo = [
                            e
                            for e in todo
                            if e.session != ev.session
                        ]
                    del live[key]
        raise AssertionError("tier replay did not drain")

    tier_lat_sessions = {
        ev.session
        for ev in tier_trace.events
        if ev.tier == "latency"
    }
    tier_mixed, tier_mixed_metrics, tier_pool = _tier_replay(
        tiered=True
    )
    tier_solo, _solo_m, _solo_p = _tier_replay(
        tiered=True, sessions=tier_lat_sessions
    )
    tier_oracle, _orc_m, _orc_p = _tier_replay(tiered=False)

    tier_parity_ok = all(
        list(r.tokens) == list(tier_oracle[key].tokens)
        for key, r in tier_mixed.items()
    ) and all(
        list(r.tokens) == list(tier_mixed[key].tokens)
        for key, r in tier_solo.items()
    )
    tier_reqs = list(tier_mixed.values())
    tier_success_rate = sum(
        1 for r in tier_reqs if r.state.value == "done"
    ) / max(len(tier_reqs), 1)

    def _tier_ttfts(out):
        byturn = {
            (ev.session, ev.turn): ev for ev in tier_trace.events
        }
        return sorted(
            (r.first_token_ts - r.submit_ts) * 1000.0
            for key, r in out.items()
            if byturn[key].tier == "latency"
            and r.first_token_ts is not None
        )

    tier_mixed_ttfts = _tier_ttfts(tier_mixed)
    tier_solo_ttfts = _tier_ttfts(tier_solo)
    tier_ttft_ratio = pct(tier_mixed_ttfts, 0.99) / max(
        pct(tier_solo_ttfts, 0.99), 1e-9
    )
    tier_preemptions_total = tier_showcase_preemptions + int(
        tier_mixed_metrics.tier_preempted_total["batch"]
    )
    tier_event_counts = {
        t: sum(1 for ev in tier_trace.events if ev.tier == t)
        for t in ("latency", "standard", "batch")
    }

    # forecast leg: the generator's OWN arrival-count series (the
    # diurnal sinusoid it promises) replayed into the brain store with
    # explicit virtual timestamps; predictive_scale must hint UP
    # strictly before the arrival peak — lead time, not hindsight.
    # The replay trace above is miniaturized for CPU runtime and too
    # sparse for a slope fit, so the telemetry leg reads a
    # production-scale day from the SAME config: longer horizon, more
    # sessions, identical diurnal shape.
    import dataclasses as _dc

    tier_ftrace = generate_trace(
        _dc.replace(
            tier_cfg, horizon_s=240.0, period_s=240.0, base_rate=2.0
        )
    )
    tier_counts = tier_ftrace.arrival_counts(24)
    t_maxc = max(tier_counts)
    tier_peak_idx = max(
        range(len(tier_counts)), key=lambda i: tier_counts[i]
    )
    tadvisor = ServingScaleAdvisor(max_replicas=8)
    tier_pool.advisor = tadvisor.on_hint
    tstore = JobMetricsStore()
    tier_pool.brain_store = tstore
    tier_first_up_idx = -1
    for i, c in enumerate(tier_counts):
        t_pr = c / max(t_maxc, 1)
        tstore.add_sample(
            RuntimeSample(
                job_uuid=tier_pool.job_uuid,
                role="serving",
                num_nodes=3,
                cpu_percent=t_pr * 100.0,
                ts=10.0 * i,
                queue_depth=int(c),
            )
        )
        t_hint = tier_pool.predictive_scale()
        if (
            t_hint is not None
            and t_hint["direction"] == "up"
            and tier_first_up_idx < 0
        ):
            tier_first_up_idx = i

    # ---- phase 14: interleaved chunked prefill (one colocated rep) ----
    # Phase 9's mixed long-prefill/short-decode workload again — but
    # instead of paying a second (prefill-role) replica, ONE colocated
    # engine flips the prefill_chunk knob: blocking admission runs each
    # long prompt's whole prefill inside _admit (stalling every
    # decoder's token cadence for a full forward), interleaved
    # admission streams it through the fused chunk program a bounded
    # budget at a time, decode riding the same dispatch. Same model,
    # same prompts, same measurement discipline (decode TPOT p99 over
    # the SHORT requests, min over back-to-back cycles). Locks:
    # interleaved p99 at most half of blocking, byte parity across
    # all four runs, success 1.0 — TPOT bounded without disagg's
    # second replica, DEVIATIONS §19.
    il_chunk_tokens = 128 if on_tpu else 64

    def _interleave_perf(pc):
        imetrics = ServingMetrics()
        ieng = ContinuousBatcher(
            dcfg, dparams, n_slots=d_slots, max_len=d_max_len,
            max_new_tokens=max(d_short_new, d_long_new),
            chunk=d_chunk, pad_id=-1, kv_layout="paged",
            prefill_chunk=pc,
        )
        isch = RequestScheduler(ieng, d_slo, metrics=imetrics)
        # warm outside the timed region: short + long prefill buckets
        # (blocking leg) / every pow2 chunk length the long prompt
        # decomposes into (interleaved leg), plus the chunk scan
        for p, mn in (
            (d_short_prompts[0], 2),
            (d_long_prompts[0], 2),
        ):
            isch.submit(p, max_new=mn)
            isch.run_to_completion()
        stall0 = ieng.prefill_stats()["admission_stall_ms"]
        stop = threading.Event()
        th = threading.Thread(
            target=_pump_loop, args=(isch, stop), daemon=True
        )
        th.start()
        sreqs = [
            isch.submit(p, max_new=d_short_new, deadline_s=600.0)
            for p in d_short_prompts
        ]
        # longs land once every short is mid-decode, so their
        # prefills contend with the shorts' cadence by construction
        t_dead = time.monotonic() + 120.0
        while time.monotonic() < t_dead and any(
            r.first_token_ts is None for r in sreqs
        ):
            time.sleep(0.001)
        lreqs = [
            isch.submit(p, max_new=d_long_new, deadline_s=600.0)
            for p in d_long_prompts
        ]
        for r in sreqs + lreqs:
            r.wait(timeout=300.0)
        stop.set()
        th.join(timeout=10.0)
        itpots = sorted(
            (r.finish_ts - r.first_token_ts)
            * 1000.0
            / (len(r.tokens) - 1)
            for r in sreqs
            if r.first_token_ts is not None and len(r.tokens) > 1
        )
        outs = [list(r.tokens) for r in sreqs + lreqs]
        done = sum(
            1 for r in sreqs + lreqs if r.state.value == "done"
        )
        pstats = ieng.prefill_stats()
        pstats["admission_stall_ms"] -= stall0  # timed region only
        return pct(itpots, 0.99), outs, done, pstats

    il_block_runs = [_interleave_perf(0) for _ in range(2)]
    il_runs = [_interleave_perf(il_chunk_tokens) for _ in range(2)]
    il_block_p99 = min(r[0] for r in il_block_runs)
    il_p99 = min(r[0] for r in il_runs)
    il_parity_ok = all(
        r[1] == il_block_runs[0][1]
        for r in il_block_runs + il_runs
    )
    il_success_rate = min(
        r[2] / (n_d_short + n_d_long)
        for r in il_block_runs + il_runs
    )
    il_stats = il_runs[-1][3]
    il_block_stats = il_block_runs[-1][3]

    # ---- phase 15: host-DRAM KV tier (serving/kv_tier.py) -------------
    # The missing rung of the memory hierarchy behind the prefix
    # cache: a working set of tenant system prompts SEVERAL TIMES the
    # device prefix pool (prefix_cache_rows=1) churns through a
    # byte-capacity host tier. Round 1 publishes each tenant cold —
    # every publish LRU-evicts the previous tenant's row, which the
    # tiered engine demotes to host DRAM and the untiered one drops.
    # Round 2 revisits every tenant: untiered pays the full cold
    # re-prefill, tiered promotes the stored bytes back over PCIe.
    # Locks: tiered warm TTFT p50 strictly under the untiered cold
    # re-prefill p50 (PCIe beats recompute at the FLOPs-dominant
    # scale), a promote hit-rate floor, byte parity (the tier never
    # changes a token), success 1.0 — and, on the paged pressure leg,
    # at least one preempted victim resumed from host bytes instead
    # of replay. DEVIATIONS §20.
    kt_tenants = 8 if on_tpu else 6
    kt_rows = 1
    ktrng = np.random.default_rng(15)
    kt_prefixes = [
        ktrng.integers(
            1, min(500, pcfg.vocab_size), size=sys_len
        ).tolist()
        for _ in range(kt_tenants + 2)  # +2 warm-up tenants
    ]
    kt_tails = [
        [
            ktrng.integers(
                1, min(500, pcfg.vocab_size), size=int(t)
            ).tolist()
            for t in ktrng.integers(2, 9, size=kt_tenants)
        ]
        for _ in range(2)  # distinct per-round turn suffixes
    ]

    def _kt_ttft_pass(tier_bytes):
        """Drive the churn workload one request at a time (TTFT =
        admission + first chunk, no queue wait). Returns the engine,
        every output stream, per-round sorted TTFTs, and whether all
        requests completed."""
        kteng = ContinuousBatcher(
            pcfg, pparams, n_slots=p_slots, max_len=p_max_len,
            max_new_tokens=p_max_new, chunk=p_chunk, pad_id=-1,
            prefix_cache_rows=kt_rows, kv_tier_bytes=tier_bytes,
        )
        ktsched = RequestScheduler(
            kteng,
            SloConfig(
                max_queue_depth=2 * kt_tenants + 4,
                max_new_tokens=p_max_new,
                default_deadline_s=600.0,
            ),
            metrics=ServingMetrics(),
        )
        kt_outs = []
        kt_ok = [True]

        def _one(prompt, ttfts=None):
            r = ktsched.submit(prompt, max_new=p_max_new)
            ktsched.run_to_completion()
            kt_outs.append(list(r.tokens))
            kt_ok[0] &= r.state.value == "done"
            if ttfts is not None:
                ttfts.append(
                    (r.first_token_ts - r.submit_ts) * 1000.0
                )

        # warm-up: cold publish, churn-evict (demote), revisit
        # (promote) — every program the timed rounds need compiles
        # here, outside the timed region
        _one(kt_prefixes[kt_tenants])
        _one(kt_prefixes[kt_tenants + 1])
        _one(kt_prefixes[kt_tenants] + kt_tails[0][0])
        cold_ts, revisit_ts = [], []
        for rnd, ts in ((0, cold_ts), (1, revisit_ts)):
            for i in range(kt_tenants):
                _one(kt_prefixes[i] + kt_tails[rnd][i], ts)
        return (
            kteng, kt_outs, sorted(cold_ts), sorted(revisit_ts),
            kt_ok[0],
        )

    _kt0_eng, kt0_outs, _kt0_cold, kt0_revisit, kt0_ok = (
        _kt_ttft_pass(0)
    )
    kt1_eng, kt1_outs, kt1_cold, kt1_warm, kt1_ok = _kt_ttft_pass(
        256 << 20
    )
    kt_parity_ok = kt0_outs == kt1_outs
    kt_success = 1.0 if (kt0_ok and kt1_ok) else 0.0
    kt_stats = kt1_eng.kv_tier_stats()
    # the cold-prefill baseline is the UNTIERED engine's revisit
    # round: the identical request stream, the only delta is the tier
    kt_cold_p50 = pct(kt0_revisit, 0.5)
    kt_warm_p50 = pct(kt1_warm, 0.5)

    # paged pressure leg: the oversubscribed pool preempts under
    # admission pressure; with the tier on, every victim must swap to
    # host and resume from the stored bytes instead of replaying
    ktsrng = np.random.default_rng(7)
    kt_swap_prompts = [
        ktsrng.integers(1, 250, size=int(n)).tolist()
        for n in ktsrng.integers(12, 30, size=8)
    ]

    def _kt_swap(tier_bytes):
        kseng = ContinuousBatcher(
            cfg, params, n_slots=3, max_len=64, max_new_tokens=12,
            chunk=4, pad_id=-1, kv_layout="paged", page_size=8,
            n_pages=14, kv_tier_bytes=tier_bytes,
        )
        ksouts = [
            [int(t) for t in o]
            for o in kseng.generate_all(kt_swap_prompts)
        ]
        return kseng, ksouts

    kts0_eng, kts0_outs = _kt_swap(0)
    kts1_eng, kts1_outs = _kt_swap(64 << 20)
    kt_swap_parity_ok = kts0_outs == kts1_outs
    kts_stats = kts1_eng.kv_tier_stats()
    kts_paged = kts1_eng.paged_stats()
    kts0_paged = kts0_eng.paged_stats()
    kt_swap_success = (
        1.0
        if kts_paged["swap_resumes"] == kts_paged["swap_preemptions"]
        and kts0_paged["swap_preemptions"] > 0
        else 0.0
    )

    # ---- phase 16: serving health sentinel (serving/health.py) --------
    # The gray-failure campaign: a 3-replica pool with preflight
    # self-checks, KV integrity checksums, and the fleet-relative
    # straggler sentinel all armed, hit mid-workload by (a) in-transit
    # KV corruption at every replica's tier egress and (b) a chaos-
    # slowed replica. Locks: success 1.0 and byte parity vs the
    # no-fault oracle arm (quarantined entries fall back to replay —
    # zero corrupted tokens ever emitted), at least one corrupt fired
    # and at least one payload quarantined, every preflight passed,
    # and the slow replica fenced within the patience window.
    # DEVIATIONS §21.
    hs_patience = 3
    hs_tenants = 6
    hsrng = np.random.default_rng(16)
    hs_prefixes = [
        hsrng.integers(1, 250, size=16).tolist()
        for _ in range(hs_tenants)
    ]
    hs_tails = [
        hsrng.integers(1, 250, size=int(t)).tolist()
        for t in hsrng.integers(3, 8, size=2 * hs_tenants)
    ]

    def _hs_run(fi, arm=None, ratio=2.5):
        """Direct-drive 3-replica health pool: prefix churn through a
        1-row radix cache backed by a checksummed host tier, pool
        health pass interleaved with every pump round. Returns
        (outputs, all-done, preflight-ok, rounds-to-fence, pool,
        replicas)."""
        hmetrics = ServingMetrics()
        hpool = ReplicaPool(
            metrics=hmetrics,
            straggler_ratio=ratio,
            straggler_patience=hs_patience,
        )
        hreps = []
        for i in range(3):
            tag = f"health-{i}"
            heng = ContinuousBatcher(
                cfg, params, n_slots=2, max_len=64,
                max_new_tokens=6, chunk=4, pad_id=-1,
                prefix_cache_rows=1, kv_tier_bytes=32 << 20,
                kv_checksums=1, chaos=fi, chaos_tag=tag,
            )
            hsched = RequestScheduler(
                heng,
                SloConfig(default_deadline_s=600.0),
                metrics=hmetrics,
            )
            hrep = InferenceReplica(tag, hsched, chaos=fi)
            hpool.add(hrep)
            hreps.append(hrep)
        # preflight self-check: every device re-derives the golden
        # digest before taking traffic (failing closed into degraded)
        hs_pf = all(hrep.run_preflight() for hrep in hreps)
        # warm-up compiles per fresh engine, injector quiescent
        for hrep in hreps:
            w = hrep.scheduler.submit(hs_prefixes[0][:8], max_new=2)
            hrep.scheduler.run_to_completion()
            assert w.state.value == "done"
        if arm is not None:
            arm(fi, hreps)
        # deterministic round-robin placement: every replica MUST
        # dispatch for the fleet-relative test to observe it (the
        # pool's load router would park this whole burst on one
        # replica and starve the detector of the very straggler it
        # is supposed to fence — routing-under-fence has its own
        # regression test). Tenant i sticks to replica i%3 across
        # both rounds so round 2 revisits promote what round 1
        # demoted, through the checksummed host tier.
        hreqs = [
            hreps[i % 3].scheduler.submit(
                hs_prefixes[i] + hs_tails[rnd * hs_tenants + i],
                max_new=6,
            )
            for rnd in range(2)
            for i in range(hs_tenants)
        ]
        fence_round = -1
        for rounds in range(1, 100_001):
            busy = False
            for hrep in hreps:
                busy = hrep.scheduler.pump() or busy
            hpool.check_replicas()
            if (
                fence_round < 0
                and hpool.health_stats().get("straggler_fenced")
            ):
                fence_round = rounds
            if not busy:
                break
        else:
            raise AssertionError("health pool did not drain")
        # the burst can drain in fewer pumps than the patience
        # window; health passes keep running on the live fleet
        # regardless (the detector evaluates the last published
        # EWMAs), so keep checking until the verdict lands
        if arm is not None:
            for _ in range(4 * hs_patience):
                if fence_round >= 0:
                    break
                rounds += 1
                hpool.check_replicas()
                if hpool.health_stats().get("straggler_fenced"):
                    fence_round = rounds
        houts = [[int(t) for t in r.tokens] for r in hreqs]
        hs_ok = all(r.state.value == "done" for r in hreqs)
        return houts, hs_ok, hs_pf, fence_round, hpool, hreps

    # the oracle arm runs detection effectively disabled (ratio far
    # above any real skew): the first pool to pump these shapes pays
    # the compile spikes, and a fleet-relative test over a 3-replica
    # fleet would misread that skew as a straggler. Routing never
    # changes token bytes, so parity is unaffected.
    hs0_outs, hs0_ok, hs0_pf, _, _, _ = _hs_run(
        FaultInjector(seed=0), ratio=1e9
    )

    def _hs_arm(fi, hreps):
        # corrupt the FIRST payload finalized at every replica's tier
        # egress (round 2's revisit promotes demoted rows — whichever
        # replica serves one from host bytes trips the checksum), and
        # stall replica health-2 into a straggler from here on
        for i in range(3):
            fi.corrupt_kv(f"health-{i}#kvtier", where="tier",
                          at_step=0)
        # the stall must clear the fence (2.5x the fleet-median step)
        # by a wide margin once programs are warm — CPU decode steps
        # run a few ms, so a quarter-second stall is unambiguous
        fi.slow_replica("health-2", 0.25)

    hs_fi = FaultInjector(seed=0)
    hs1_outs, hs1_ok, hs1_pf, hs_fence_round, hs_pool, hs_reps = (
        _hs_run(hs_fi, arm=_hs_arm)
    )
    hs_parity_ok = hs0_outs == hs1_outs
    hs_success = 1.0 if (hs0_ok and hs1_ok) else 0.0
    hs_quarantines = int(
        sum(
            hrep.scheduler.engine.health_stats().get(
                "integrity_quarantines", 0
            )
            for hrep in hs_reps
        )
    )
    hs_corrupt_fired = sum(
        1 for kind, _, _ in hs_fi.fired if kind == "corrupt"
    )

    # ---- weight-quant phase: int8 weight-only decode --------------------
    # The HBM-bytes claim, measured the paired way: one f32 engine and
    # one weight_quant="int8" engine over the SAME trained weights,
    # timed in ABBA order (same discipline as the paged phase). The
    # quality gate needs a trained model: random-init tiny models have
    # near-tied logits, so the argmax flips under ANY re-rounding and
    # greedy agreement measures tie-breaking noise (~96-97%), not
    # quantization error. A few dozen SGD steps on a deterministic
    # cyclic corpus separate the logit gaps (seconds on CPU) and the
    # int8 engine then agrees token-for-token.
    import dataclasses as _dc

    from dlrover_tpu.ops.quantization import (
        QuantizedWeight,
        quantized_matmul_kernel,
        quantized_matmul_reference,
    )

    wq_cfg = _dc.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    wq_params = llama.init_params(wq_cfg, jax.random.PRNGKey(0))
    wq_corpus = (
        jnp.arange(8 * 65).reshape(8, 65) * 7
        + jnp.arange(8)[:, None] * 13
    ) % 97 + 3
    wq_batch = {"tokens": wq_corpus}

    @jax.jit
    def _wq_train_step(p):
        (l, _), g = jax.value_and_grad(
            lambda q: llama.loss_fn(wq_cfg, q, wq_batch),
            has_aux=True,
        )(p)
        return (
            jax.tree_util.tree_map(lambda w, dw: w - 0.5 * dw, p, g),
            l,
        )

    wq_train_steps = 60
    wq_loss = 0.0
    for _ in range(wq_train_steps):
        wq_params, wq_loss = _wq_train_step(wq_params)
    wq_loss = float(wq_loss)

    wq_prompts = [
        [int(t) for t in wq_corpus[i % 8, : 6 + 2 * (i % 5)]]
        for i in range(8)
    ]
    wq_new = 16
    wq_slo = SloConfig(
        max_queue_depth=len(wq_prompts) + 1,
        max_new_tokens=wq_new,
        default_deadline_s=600.0,
    )
    wq_eng_f = ContinuousBatcher(
        wq_cfg, wq_params, n_slots=4, max_len=96,
        max_new_tokens=wq_new, chunk=4, pad_id=-1,
    )
    wq_eng_q = ContinuousBatcher(
        wq_cfg, wq_params, n_slots=4, max_len=96,
        max_new_tokens=wq_new, chunk=4, pad_id=-1,
        weight_quant="int8",
    )

    def _wq_pass(eng):
        timed = RequestScheduler(eng, wq_slo, metrics=ServingMetrics())
        wreqs = [timed.submit(p, max_new=wq_new) for p in wq_prompts]
        timed.run_to_completion()
        wtpots = sorted(
            (r.finish_ts - r.first_token_ts)
            * 1000.0
            / (len(r.tokens) - 1)
            for r in wreqs
            if r.first_token_ts is not None and len(r.tokens) > 1
        )
        outs = [[int(t) for t in r.tokens] for r in wreqs]
        ok = all(r.state.value == "done" for r in wreqs)
        return pct(wtpots, 0.5), outs, ok

    # warm both engines' programs outside the timed cycles
    _wq_pass(wq_eng_f)
    _wq_pass(wq_eng_q)
    _wq_f_p50s, _wq_q_p50s = [], []
    wq_outs_f = wq_outs_q = None
    wq_ok = True
    for i in range(4):
        arms = (
            ((wq_eng_f, _wq_f_p50s), (wq_eng_q, _wq_q_p50s))
            if i % 2 == 0
            else ((wq_eng_q, _wq_q_p50s), (wq_eng_f, _wq_f_p50s))
        )
        for eng, sink in arms:
            p50, outs, ok = _wq_pass(eng)
            sink.append(p50)
            wq_ok = wq_ok and ok
            if eng is wq_eng_f:
                wq_outs_f = outs
            else:
                wq_outs_q = outs
    wq_success = 1.0 if wq_ok else 0.0
    # token-level greedy agreement over paired streams; a length
    # mismatch counts every missing tail token as a disagreement
    _wq_tok_total = sum(
        max(len(a), len(b)) for a, b in zip(wq_outs_f, wq_outs_q)
    )
    _wq_tok_match = sum(
        1
        for a, b in zip(wq_outs_f, wq_outs_q)
        for x, y in zip(a, b)
        if x == y
    )
    wq_agreement = _wq_tok_match / max(_wq_tok_total, 1)
    # paired-median TPOT ratio (recorded evidence, not a perf lock:
    # on CPU the dequant work dominates the saved bytes, so the ratio
    # only becomes a claim on a real HBM-bound chip)
    _wq_ratios = sorted(
        q / max(f, 1e-9) for f, q in zip(_wq_f_p50s, _wq_q_p50s)
    )
    _wn = len(_wq_ratios)
    wq_pair_ratio = (
        _wq_ratios[_wn // 2]
        if _wn % 2
        else 0.5 * (_wq_ratios[_wn // 2 - 1] + _wq_ratios[_wn // 2])
    )
    wq_bytes_f = wq_eng_f.weight_bytes_device()
    wq_bytes_q = wq_eng_q.weight_bytes_device()
    wq_bytes_ratio = wq_bytes_q / max(wq_bytes_f, 1)
    # kernel-vs-reference parity on a quantized leaf straight out of
    # the engine's installed tree. In interpret mode the kernel grid
    # collapses to the reference's exact op sequence, so parity is
    # BYTE equality; on a real chip the tiled grid reassociates the
    # f32 accumulation and the check is allclose at f32 resolution.
    _wq_leaf = next(
        leaf
        for leaf in jax.tree_util.tree_leaves(
            wq_eng_q.params,
            is_leaf=lambda x: isinstance(x, QuantizedWeight),
        )
        if isinstance(leaf, QuantizedWeight)
    )
    _wq_w0 = jax.tree_util.tree_map(lambda a: a[0], _wq_leaf)
    _wq_x = jax.random.normal(
        jax.random.PRNGKey(1), (4, _wq_w0.shape[-2]), jnp.float32
    )
    _wq_kern = np.asarray(quantized_matmul_kernel(_wq_x, _wq_w0))
    _wq_ref = np.asarray(quantized_matmul_reference(_wq_x, _wq_w0))
    if jax.default_backend() == "cpu":
        wq_kernel_parity_ok = bool(
            _wq_kern.tobytes() == _wq_ref.tobytes()
        )
    else:
        wq_kernel_parity_ok = bool(
            np.allclose(_wq_kern, _wq_ref, rtol=1e-5, atol=1e-5)
        )
    wq_path = wq_eng_q.weight_quant_path
    # main-engine footprint telemetry (the none path): served tok/s
    # normalized by resident weight GB, the cross-run capacity axis
    main_weight_bytes = engine.weight_bytes_device()
    tok_per_weight_gb = (
        cont_tps / (main_weight_bytes / 1e9)
        if main_weight_bytes
        else 0.0
    )

    print(
        json.dumps(
            {
                "metric": "serve_tokens_per_sec",
                "value": round(cont_tps, 1),
                "unit": "tok/s",
                "vs_baseline": round(cont_tps / base_tps, 3)
                if base_tps > 0
                else 0.0,
                "detail": {
                    "backend": jax.default_backend(),
                    "device": {
                        "platform": platform,
                        "kind": jax.devices()[0].device_kind,
                        "count": len(jax.devices()),
                    },
                    "ttft_ms_p50": round(pct(ttfts, 0.5), 2),
                    "ttft_ms_p95": round(pct(ttfts, 0.95), 2),
                    "tpot_ms_mean": round(
                        sum(tpots) / len(tpots), 3
                    )
                    if tpots
                    else 0.0,
                    "throughput_tok_s": round(cont_tps, 1),
                    "lockstep_tok_s": round(base_tps, 1),
                    "n_requests": n_requests,
                    "n_slots": n_slots,
                    "max_new": max_new,
                    "served_tokens": served_tokens,
                    "shed_total": metrics.shed_total,
                    "completed": metrics.completed_total,
                    # shared-system-prompt phase: prefix-cache reuse
                    "prefix_hit_rate": round(
                        pc_stats["hit_rate"], 3
                    ),
                    "prefix_tokens_reused": pc_stats[
                        "tokens_reused"
                    ],
                    "prefix_evictions": pc_stats["evictions"],
                    "prefix_pool_rows": pc_stats["rows_total"],
                    "sys_prompt_len": sys_len,
                    "n_prefix_requests": n_prefix_reqs,
                    "ttft_cold_ms_p50": round(
                        pct(cold_ttfts, 0.5), 2
                    ),
                    "ttft_cold_ms_p95": round(
                        pct(cold_ttfts, 0.95), 2
                    ),
                    "ttft_warm_ms_p50": round(
                        pct(warm_ttfts, 0.5), 2
                    ),
                    "ttft_warm_ms_p95": round(
                        pct(warm_ttfts, 0.95), 2
                    ),
                    # speculative phase: n-gram drafting off vs on
                    "spec_tpot_ms_p50": round(
                        pct(spec_tpots, 0.5), 3
                    ),
                    "spec_baseline_tpot_ms_p50": round(
                        pct(spec_base_tpots, 0.5), 3
                    ),
                    "spec_accept_rate": round(
                        spec_stats["acceptance_rate"], 3
                    ),
                    "spec_accepted_per_step": round(
                        spec_stats["accepted_per_step"], 3
                    ),
                    "spec_tokens_per_step": round(
                        spec_stats["tokens_per_step"], 3
                    ),
                    "spec_draft_len": spec_k,
                    "n_spec_requests": len(spec_prompts),
                    # overlap phase: async dispatch off vs on
                    "sync_tpot_ms_p50": round(sync_tpot_p50, 3),
                    "async_tpot_ms_p50": round(async_tpot_p50, 3),
                    "async_overlap_ratio": round(
                        async_overlap_ratio, 3
                    ),
                    "async_parity_ok": async_parity_ok,
                    "chaos_async_depth": 1,
                    # chaos phase: replica death mid-decode
                    "chaos_success_rate": round(
                        chaos_success_rate, 3
                    ),
                    "chaos_parity_ok": chaos_parity_ok,
                    "chaos_failovers": chaos_metrics.failovers_total,
                    "chaos_replica_ejections": (
                        chaos_metrics.replica_ejections
                    ),
                    "chaos_failed_total": chaos_metrics.failed_total,
                    "steady_ttft_p99_ms": round(
                        pct(steady_ttfts, 0.99), 2
                    ),
                    "chaos_ttft_p99_ms": round(
                        pct(chaos_ttfts, 0.99), 2
                    ),
                    "chaos_ttft_p99_ratio": round(
                        pct(chaos_ttfts, 0.99)
                        / max(pct(steady_ttfts, 0.99), 1e-9),
                        3,
                    ),
                    "n_chaos_requests": len(chaos_reqs),
                    # paged phase: paged KV layout evidence axes
                    "dense_tpot_ms_p50": round(
                        paged_dense_tpot_p50, 3
                    ),
                    "paged_tpot_ms_p50": round(paged_tpot_p50, 3),
                    # paired (median over ABBA cycles), NOT the ratio
                    # of the two minima above — see the measurement
                    # comment in the paged phase
                    "paged_tpot_ratio": round(paged_pair_ratio, 3),
                    "paged_parity_ok": paged_parity_ok,
                    "paged_success_rate": round(
                        paged_success_rate, 3
                    ),
                    "paged_swap_preemptions": int(
                        oversub_stats["swap_preemptions"]
                    ),
                    "paged_swap_resumes": int(
                        oversub_stats["swap_resumes"]
                    ),
                    "paged_oversub_pool_pages": oversub_pages,
                    "paged_pages_per_slot": per_slot,
                    "paged_page_size": oversub_eng.page_size,
                    "paged_warm_cow_copies": int(paged_warm_cow),
                    "paged_pages_shared": int(
                        share_stats["pages_shared"]
                    ),
                    "paged_prefix_hit_rate": round(
                        paged_hit_rate, 3
                    ),
                    "n_paged_requests": len(oversub_out),
                    # mesh phase: tensor-parallel slice evidence axes
                    "mesh_tp": mesh_tp,
                    "mesh_devices": mesh_devices,
                    "mesh_tp1_tpot_ms_p50": round(
                        mesh_tp1_tpot_p50, 3
                    ),
                    "mesh_tp2_tpot_ms_p50": round(
                        mesh_tp2_tpot_p50, 3
                    ),
                    "mesh_parity_ok": mesh_parity_ok,
                    "mesh_metrics_ok": mesh_metrics_ok,
                    "n_mesh_requests": n_mesh_requests,
                    # kernel phase: fused-dispatch evidence axes
                    "kernel_path": kernel_path,
                    "kernel_path_ok": kernel_path_ok,
                    "kernel_metrics_ok": kernel_metrics_ok,
                    "kernel_forced_path_ok": kernel_forced_path_ok,
                    "kernel_parity_ok": kernel_parity_ok,
                    "kernel_tpot_ms": round(kernel_tpot_ms, 3),
                    "kernel_ref_tpot_ms": round(
                        kernel_ref_tpot_ms, 3
                    ),
                    "kernel_tpot_ratio": round(kernel_tpot_ratio, 3),
                    "n_kernel_requests": len(kern_out),
                    # disaggregation phase: MPMD phase-split evidence
                    "disagg_coloc_tpot_p99_ms": round(
                        disagg_coloc_p99, 3
                    ),
                    "disagg_tpot_p99_ms": round(disagg_p99, 3),
                    "disagg_tpot_p99_ratio": round(
                        disagg_p99 / max(disagg_coloc_p99, 1e-9), 3
                    ),
                    "disagg_parity_ok": disagg_parity_ok,
                    "disagg_success_rate": round(
                        disagg_success_rate, 3
                    ),
                    "disagg_crash_success_rate": round(
                        disagg_crash_success, 3
                    ),
                    "disagg_crash_leaked_pages": disagg_crash_leaked,
                    "disagg_handoffs": disagg_handoffs,
                    "disagg_pages_adopted": disagg_pages_adopted,
                    "n_disagg_requests": n_disagg_total,
                    # elastic phase: chip-loss shrink + drain-free
                    # weight refresh evidence axes
                    "elastic_tp": elastic_tp,
                    "elastic_resized_tp": elastic_resized_tp,
                    "elastic_success_rate": round(
                        elastic_success_rate, 3
                    ),
                    "elastic_parity_ok": elastic_parity_ok,
                    "elastic_replayed": elastic_replayed,
                    "elastic_downtime_ms": round(
                        elastic_downtime_ms, 3
                    ),
                    "elastic_refresh_ok": elastic_refresh_ok,
                    "elastic_metrics_ok": elastic_metrics_ok,
                    "n_elastic_requests": n_elastic_requests,
                    # adapter phase: multi-tenant LoRA evidence axes
                    "adapter_mix_tpot_ms_p50": round(
                        adapter_mix_tpot_p50, 3
                    ),
                    "adapter_single_tpot_ms_p50": round(
                        adapter_single_tpot_p50, 3
                    ),
                    # paired (median over ABBA cycles), same
                    # measurement discipline as paged_tpot_ratio
                    "adapter_tpot_ratio": round(
                        adapter_pair_ratio, 3
                    ),
                    "adapter_parity_ok": adapter_parity_ok,
                    "adapter_cache_hit_rate": round(
                        adapter_hit_rate, 3
                    ),
                    "adapter_cache_evictions": int(
                        a_stats["evictions"]
                    ),
                    "adapter_uploads": int(a_stats["uploads"]),
                    "n_adapters": n_adapters,
                    "adapter_cache_slots": adapter_cache_slots,
                    "n_adapter_requests": len(amix_out),
                    # fleet phase: prefix-affinity routing +
                    # predictive autoscaling evidence axes
                    "fleet_hit_rate": round(fleet_hit_rate, 3),
                    "fleet_lb_hit_rate": round(
                        fleet_lb_hit_rate, 3
                    ),
                    "fleet_single_hit_rate": round(
                        fleet_single_hit_rate, 3
                    ),
                    "fleet_ttft_ms_p50": round(
                        pct(fleet_ttfts, 0.5), 2
                    ),
                    "fleet_ttft_ms_p90": round(
                        pct(fleet_ttfts, 0.9), 2
                    ),
                    "fleet_ttft_ms_mean": round(
                        sum(fleet_ttfts) / len(fleet_ttfts), 2
                    )
                    if fleet_ttfts
                    else 0.0,
                    "fleet_lb_ttft_ms_p50": round(
                        pct(fleet_lb_ttfts, 0.5), 2
                    ),
                    "fleet_lb_ttft_ms_p90": round(
                        pct(fleet_lb_ttfts, 0.9), 2
                    ),
                    "fleet_lb_ttft_ms_mean": round(
                        sum(fleet_lb_ttfts) / len(fleet_lb_ttfts),
                        2,
                    )
                    if fleet_lb_ttfts
                    else 0.0,
                    "fleet_parity_ok": fleet_parity_ok,
                    "fleet_affinity_matched": int(
                        _fm.affinity_matched
                    ),
                    "fleet_digests": int(
                        fleet_pool.routing_stats()["digests"]
                    ),
                    "fleet_replicas": fleet_replicas,
                    "fleet_tenants": fleet_tenants,
                    "n_fleet_requests": len(fleet_rows),
                    "forecast_first_up_idx": forecast_first_up_idx,
                    "forecast_peak_idx": forecast_peak_idx,
                    "forecast_lead_samples": forecast_lead_samples,
                    "forecast_chip_delta": forecast_chip_delta,
                    "forecast_plans": int(fadvisor.forecast_plans),
                    "forecast_telemetry_ok": forecast_telemetry_ok,
                    # tier phase: priority tiers + preemption under
                    # the trace-driven workload evidence axes
                    "tier_preemptions": int(tier_preemptions_total),
                    "tier_showcase_preemptions": int(
                        tier_showcase_preemptions
                    ),
                    "tier_preempt_parity_ok": tier_preempt_parity_ok,
                    "tier_parity_ok": tier_parity_ok,
                    "tier_success_rate": round(
                        tier_success_rate, 3
                    ),
                    "tier_latency_solo_ttft_p99_ms": round(
                        pct(tier_solo_ttfts, 0.99), 2
                    ),
                    "tier_latency_mixed_ttft_p99_ms": round(
                        pct(tier_mixed_ttfts, 0.99), 2
                    ),
                    "tier_latency_ttft_p99_ratio": round(
                        tier_ttft_ratio, 3
                    ),
                    "tier_shed_total": int(
                        tier_mixed_metrics.shed_total
                    ),
                    "tier_escalations": int(
                        sum(
                            tier_mixed_metrics
                            .tier_escalated_total.values()
                        )
                    ),
                    "n_tier_latency": tier_event_counts["latency"],
                    "n_tier_standard": tier_event_counts[
                        "standard"
                    ],
                    "n_tier_batch": tier_event_counts["batch"],
                    "trace_events": len(tier_trace.events),
                    "trace_sessions": tier_trace.n_sessions,
                    "trace_multi_turn_sessions": len(
                        {
                            ev.session
                            for ev in tier_trace.events
                            if ev.n_turns > 1
                        }
                    ),
                    "trace_long_context_sessions": len(
                        {
                            ev.session
                            for ev in tier_trace.events
                            if ev.long_context
                        }
                    ),
                    "trace_forecast_first_up_idx": (
                        tier_first_up_idx
                    ),
                    "trace_forecast_peak_idx": tier_peak_idx,
                    "trace_forecast_lead_buckets": (
                        tier_peak_idx - tier_first_up_idx
                        if tier_first_up_idx >= 0
                        else -1
                    ),
                    # interleave phase: chunked prefill on one
                    # colocated replica evidence axes
                    "interleave_blocking_tpot_p99_ms": round(
                        il_block_p99, 3
                    ),
                    "interleave_tpot_p99_ms": round(il_p99, 3),
                    "interleave_tpot_p99_ratio": round(
                        il_p99 / max(il_block_p99, 1e-9), 3
                    ),
                    "interleave_parity_ok": il_parity_ok,
                    "interleave_success_rate": round(
                        il_success_rate, 3
                    ),
                    "interleave_prefill_chunk": il_chunk_tokens,
                    "interleave_chunks_total": int(
                        il_stats["prefill_chunks_total"]
                    ),
                    "interleave_stall_ms": round(
                        il_stats["admission_stall_ms"], 3
                    ),
                    "interleave_blocking_stall_ms": round(
                        il_block_stats["admission_stall_ms"], 3
                    ),
                    "n_interleave_requests": (
                        n_d_short + n_d_long
                    ),
                    # kv-tier phase: host-DRAM tier evidence axes
                    "kvtier_cold_ttft_ms_p50": round(
                        kt_cold_p50, 2
                    ),
                    "kvtier_warm_ttft_ms_p50": round(
                        kt_warm_p50, 2
                    ),
                    "kvtier_ttft_ratio": round(
                        kt_warm_p50 / max(kt_cold_p50, 1e-9), 3
                    ),
                    "kvtier_parity_ok": kt_parity_ok,
                    "kvtier_success_rate": kt_success,
                    "kvtier_promote_hit_rate": round(
                        kt_stats["promote_hit_rate"], 3
                    ),
                    "kvtier_demotions": int(kt_stats["demotions"]),
                    "kvtier_promotions": int(
                        kt_stats["promotions"]
                    ),
                    "kvtier_working_set_x": int(
                        kt_tenants // kt_rows
                    ),
                    "kvtier_swap_outs": int(
                        kts_stats["swap_outs"]
                    ),
                    "kvtier_swap_ins": int(kts_stats["swap_ins"]),
                    "kvtier_swap_parity_ok": kt_swap_parity_ok,
                    "kvtier_swap_success_rate": kt_swap_success,
                    "n_kvtier_requests": (
                        2 * (2 * kt_tenants + 3)
                        + 2 * len(kt_swap_prompts)
                    ),
                    # health-sentinel phase: gray-failure campaign
                    # evidence axes
                    "health_success_rate": hs_success,
                    "health_parity_ok": hs_parity_ok,
                    "health_quarantines": hs_quarantines,
                    "health_corrupt_fired": int(hs_corrupt_fired),
                    "health_straggler_fenced_pumps": int(
                        hs_fence_round
                    ),
                    "health_straggler_patience": int(hs_patience),
                    "health_preflight_ok": bool(hs0_pf and hs1_pf),
                    "n_health_requests": 2 * (2 * hs_tenants + 3),
                    # weight-quant phase: int8 weight-only decode
                    # evidence axes
                    "weight_bytes_device": int(main_weight_bytes),
                    "tok_per_sec_per_weight_gb": round(
                        tok_per_weight_gb, 1
                    ),
                    "wq_success_rate": wq_success,
                    "wq_greedy_agreement": round(wq_agreement, 4),
                    "wq_weight_bytes_f32": int(wq_bytes_f),
                    "wq_weight_bytes_int8": int(wq_bytes_q),
                    "wq_weight_bytes_ratio": round(
                        wq_bytes_ratio, 3
                    ),
                    "wq_kernel_parity_ok": wq_kernel_parity_ok,
                    "wq_path": wq_path,
                    "wq_f32_tpot_ms_p50": round(
                        min(_wq_f_p50s), 3
                    ),
                    "wq_tpot_ms_p50": round(min(_wq_q_p50s), 3),
                    # paired (median over ABBA cycles), same
                    # measurement discipline as paged_tpot_ratio;
                    # recorded, never locked < 1 on CPU
                    "wq_tpot_ratio": round(wq_pair_ratio, 3),
                    "wq_train_steps": wq_train_steps,
                    "wq_train_loss": round(wq_loss, 4),
                    "n_wq_requests": len(wq_prompts),
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
