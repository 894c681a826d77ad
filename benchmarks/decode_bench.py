"""Decode/KV-cache microbench: prefill + per-token decode tokens/s,
cached vs uncached generation (VERDICT r3 missing #4 / task #5).

The KV-cache path (models/decode.py, wired into PPO rollouts via
rl/generate.py) is correctness-tested; this publishes its SPEED — the
entire point of caching (reference: the vLLM inference backend,
atorch/rl/inference_backend/vllm_backend.py).

Run (real chip):  python benchmarks/decode_bench.py
CPU smoke:        DLROVER_TPU_FORCE_CPU=1 python benchmarks/decode_bench.py
Prints one JSON line per measurement.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.utils.platform import ensure_cpu_if_forced  # noqa: E402

ensure_cpu_if_forced()


def main():
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import decode, llama
    from dlrover_tpu.utils.prof import device_fence, timed_with_fence

    def timed(thunk, iters):
        # fence via a data-dependent scalar read, minus the fence's
        # own cost
        dt, _ = timed_with_fence(thunk, iters=iters)
        return dt

    on_tpu = False
    try:
        on_tpu = jax.default_backend() not in ("cpu",)
    except Exception:  # noqa: BLE001
        pass

    if on_tpu:
        # the flagship bench model (bench.py) minus remat (inference)
        cfg = llama.LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=24, n_heads=8,
            n_kv_heads=8, mlp_dim=4096, max_seq_len=2048,
            remat=False, attn_impl="auto",
        )
        batch, prompt_len, new_tokens = 8, 512, 128
    else:
        cfg = llama.LlamaConfig.tiny()
        batch, prompt_len, new_tokens = 2, 16, 8

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab_size
    )
    max_len = prompt_len + new_tokens

    def emit(metric, tok_per_s, **detail):
        print(
            json.dumps(
                {
                    "metric": f"decode.{metric}",
                    "value": round(tok_per_s, 1),
                    "unit": "tok/s",
                    "backend": jax.default_backend(),
                    "batch": batch,
                    "prompt_len": prompt_len,
                    "new_tokens": new_tokens,
                    **detail,
                }
            )
        )

    # ---- prefill ---------------------------------------------------------
    pf = jax.jit(
        lambda p, t, c: decode.prefill(cfg, p, t, c),
        static_argnums=(),
    )
    cache0 = decode.init_kv_cache(cfg, batch, max_len)
    logits, cache = pf(params, prompt, cache0)  # compile
    device_fence(logits)
    iters = 5 if on_tpu else 2
    # fence only the logits leaf (one jit program computes both
    # outputs, so its completion covers the cache too); keep the last
    # call's cache instead of paying one more prefill to recover it
    box = {}

    def _pf():
        lg, c = pf(params, prompt, cache0)
        box["cache"] = c
        return lg

    dt = timed(_pf, iters)
    emit("prefill", batch * prompt_len / dt, ms_per_call=round(dt * 1e3, 1))
    cache = box["cache"]

    # ---- per-token cached decode ----------------------------------------
    ds = jax.jit(
        lambda p, tok, c, pos: decode.decode_step(cfg, p, tok, c, pos)
    )
    tok = prompt[:, -1]
    lg, cache1 = ds(params, tok, cache, prompt_len)  # compile
    device_fence(lg)
    # the decode chain threads (position, cache) through the loop; one
    # timed_with_fence "iteration" runs a whole chain and the per-token
    # time divides out. The chain runs twice (warmup + timed), so cap
    # steps at new_tokens//2 to stay inside the cache's capacity.
    steps = min(64 if on_tpu else 8, new_tokens // 2)
    pos_box = {"c": cache, "i": 0}

    def _chain():
        lg = None
        for _ in range(steps):
            lg, pos_box["c"] = ds(
                params, tok, pos_box["c"], prompt_len + pos_box["i"]
            )
            pos_box["i"] += 1
        return lg

    chain_s, _ = timed_with_fence(_chain, iters=1, warmup=1)
    dt = chain_s / steps
    emit(
        "decode_per_token",
        batch / dt,
        ms_per_token=round(dt * 1e3, 2),
    )
    dt_full = dt

    # ---- per-token decode, int8 KV cache --------------------------------
    # decode attention reads the whole cache every step; the int8
    # cache halves those bytes (the HBM-bound leg on chip)
    cache_q0 = decode.init_kv_cache(cfg, batch, max_len, quant=True)
    lgq, cache_q = jax.jit(
        lambda p, t, c: decode.prefill(cfg, p, t, c)
    )(params, prompt, cache_q0)
    device_fence(lgq)
    dsq = jax.jit(
        lambda p, tok, c, pos: decode.decode_step(cfg, p, tok, c, pos)
    )
    lgq, cache_q1 = dsq(params, tok, cache_q, prompt_len)  # compile
    device_fence(lgq)
    qpos_box = {"c": cache_q, "i": 0}

    def _chain_q():
        lg = None
        for _ in range(steps):
            lg, qpos_box["c"] = dsq(
                params, tok, qpos_box["c"],
                prompt_len + qpos_box["i"],
            )
            qpos_box["i"] += 1
        return lg

    chain_s, _ = timed_with_fence(_chain_q, iters=1, warmup=1)
    dt = chain_s / steps
    emit(
        "decode_per_token_kv_quant",
        batch / dt,
        ms_per_token=round(dt * 1e3, 2),
        speedup_vs_full=round(dt_full / max(dt, 1e-9), 2),
        cache_bytes_ratio=round(
            sum(v.nbytes for v in cache_q0.values())
            / sum(
                v.nbytes
                for v in decode.init_kv_cache(
                    cfg, batch, max_len
                ).values()
            ),
            3,
        ),
    )

    # ---- generate: cached scan vs uncached full re-forward ---------------
    gen = jax.jit(
        lambda p, pr: decode.generate(
            cfg, p, pr, max_new_tokens=new_tokens, max_len=max_len
        )
    )
    out = gen(params, prompt)  # compile
    device_fence(out)
    t0 = time.monotonic()
    out = gen(params, prompt)
    device_fence(out)
    dt_cached = time.monotonic() - t0
    emit(
        "generate_cached",
        batch * new_tokens / dt_cached,
        s_per_call=round(dt_cached, 2),
    )

    # uncached: re-run the FULL forward over the growing sequence per
    # new token (what rollouts cost before models/decode.py landed).
    # One compile per length would be unfair; pad to max_len once so a
    # single compiled forward serves every step.
    fwd = jax.jit(lambda p, t: llama.apply(cfg, p, t))
    padded = jnp.pad(prompt, ((0, 0), (0, new_tokens)))
    lg = fwd(params, padded)  # compile
    device_fence(lg)
    t0 = time.monotonic()
    seq = padded
    for i in range(new_tokens):
        lg = fwd(params, seq)
        nxt = jnp.argmax(lg[:, prompt_len - 1 + i], axis=-1)
        seq = seq.at[:, prompt_len + i].set(nxt)
    device_fence(seq)
    dt_uncached = time.monotonic() - t0
    emit(
        "generate_uncached",
        batch * new_tokens / dt_uncached,
        s_per_call=round(dt_uncached, 2),
        speedup_cached=round(dt_uncached / max(dt_cached, 1e-9), 2),
    )

    # ---- mixed-length serving: continuous batching vs lockstep ----------
    # r5 (VERDICT missing #3): at MIXED request lengths a lockstep
    # batch burns steps on finished rows (everyone runs to the
    # longest request); the slot engine (rl/serve.py) re-admits on
    # release. Metric = useful generated tokens / wall second over an
    # identical request set; target >=2x at this mix.
    from dlrover_tpu.rl.serve import ContinuousBatcher

    rng = np.random.default_rng(42)
    # the serve scenario needs a REAL length spread to mean anything,
    # so it sizes itself independently of the microbench params (the
    # CPU smoke's 8-token generations cannot express a length mix)
    n_req = 48
    serve_batch = batch if on_tpu else 4
    serve_new = new_tokens if on_tpu else 64
    mix_prompt_max = prompt_len if on_tpu else 24
    serve_max_len = (
        max_len if on_tpu else mix_prompt_max + serve_new
    )
    req_prompts = [
        rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
        for n in rng.integers(4, mix_prompt_max, size=n_req)
    ]
    # long-tail rollout mix: most sequences stop early (EOS-style),
    # a minority run long — the realistic PPO traffic where lockstep
    # burns the most steps (every batch runs to its longest request)
    short_hi = max(serve_new // 8, 3)
    req_new = [
        int(rng.integers(2, short_hi))
        if rng.random() < 0.75
        else int(rng.integers(serve_new // 2, serve_new))
        for _ in range(n_req)
    ]
    useful = sum(req_new)

    # lockstep baseline: batches in submission order (a serving tier
    # cannot length-sort a live queue), padded to the batch's longest
    # prompt, run to the batch's longest max_new. jit-cached per
    # shape and warmed first so compiles don't count against it.
    jit_gen = jax.jit(
        decode.generate,
        static_argnames=("cfg", "max_new_tokens", "max_len"),
    )

    def _lockstep_pass():
        lk = None
        for i in range(0, n_req, serve_batch):
            chunk_p = req_prompts[i : i + serve_batch]
            chunk_n = req_new[i : i + serve_batch]
            pmax = max(len(p) for p in chunk_p)
            arr = np.zeros((len(chunk_p), pmax), np.int32)
            for j, p in enumerate(chunk_p):
                arr[j, : len(p)] = p
            lk = jit_gen(
                cfg=cfg, params=params, prompt=jnp.asarray(arr),
                max_new_tokens=max(chunk_n), max_len=serve_max_len,
            )
        return lk

    device_fence(_lockstep_pass())  # warm every chunk's compile
    t0 = time.monotonic()
    device_fence(_lockstep_pass())
    dt_lockstep = time.monotonic() - t0

    cb = ContinuousBatcher(
        cfg, params, n_slots=serve_batch, max_len=serve_max_len,
        max_new_tokens=serve_new, chunk=8,
    )
    for p, n in zip(req_prompts, req_new):
        cb.submit(p, max_new=n)
    cb.generate_all([])  # warm compile (prefill buckets + chunk)
    for p, n in zip(req_prompts, req_new):
        cb.submit(p, max_new=n)
    t0 = time.monotonic()
    cb.generate_all([])
    dt_cb = time.monotonic() - t0
    emit(
        "serve_mixed_continuous_batching",
        useful / dt_cb,
        # the serve scenario sizes itself; override the microbench
        # metadata so the published row describes the real experiment
        batch=serve_batch,
        prompt_len=mix_prompt_max,
        new_tokens=serve_new,
        lockstep_tok_per_s=round(useful / dt_lockstep, 1),
        speedup_vs_lockstep=round(dt_lockstep / max(dt_cb, 1e-9), 2),
        n_requests=n_req,
        s_continuous=round(dt_cb, 2),
        s_lockstep=round(dt_lockstep, 2),
    )


if __name__ == "__main__":
    main()
