"""Flash-attention microbench: the Pallas kernel vs the XLA reference.

Measures fwd and fwd+bwd step time across sequence lengths and head
dims on whatever backend is live (designed for the real TPU chip; CPU
runs the reference path only and is a smoke check). r3 full-model
context: flash vs XLA reference was 0.559 vs 0.287 MFU on the bench
Llama (bench.py) — this isolates the kernel's share.

Run: python benchmarks/flash_attention_bench.py [--quick]
Prints one JSON line per config. Reference bar: tfplus's CUDA fmha op
(tfplus/flash_attn/kernels/flash_attention_fwd_kernel.cc:172) exists
for exactly this speedup.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.utils.platform import ensure_cpu_if_forced

ensure_cpu_if_forced()

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.attention import dot_product_attention
from dlrover_tpu.ops.flash_attention import supports
from dlrover_tpu.utils.prof import device_fence, timed_with_fence


def _time_fn(fn, *args, iters=10, warmup=2):
    # fence with a data-dependent scalar read and subtract the fence's
    # own round-trip cost (timed_with_fence does both)
    dt, _ = timed_with_fence(
        lambda: fn(*args), iters=iters, warmup=warmup
    )
    return dt


def bench_config(b, s, h, d, iters):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, h, d), jnp.bfloat16)

    # causal attention FLOPs: 2 matmuls * (s^2/2 masked) * h * d * b,
    # fwd only; bwd adds ~2.5x
    flops_fwd = 2 * 2 * b * h * d * (s * s / 2)

    on_cpu = jax.default_backend() == "cpu"
    out = {"batch": b, "seq": s, "heads": h, "head_dim": d,
           "flash_supported": bool(supports(q, k)) and not on_cpu}
    for impl in ("flash", "reference"):
        if impl == "flash" and not out["flash_supported"]:
            # on CPU the flash kernel runs in Pallas interpret mode —
            # minutes-long and meaningless; reference-only smoke there
            continue
        try:
            fwd = jax.jit(
                lambda q, k, v, impl=impl: dot_product_attention(
                    q, k, v, causal=True, impl=impl
                )
            )
            t_fwd = _time_fn(fwd, q, k, v, iters=iters)

            def loss(q, k, v, impl=impl):
                return dot_product_attention(
                    q, k, v, causal=True, impl=impl
                ).astype(jnp.float32).sum()

            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            t_bwd = _time_fn(grad, q, k, v, iters=iters)
            out[f"{impl}_fwd_ms"] = round(t_fwd * 1e3, 3)
            out[f"{impl}_fwdbwd_ms"] = round(t_bwd * 1e3, 3)
            out[f"{impl}_fwd_tflops"] = round(
                flops_fwd / t_fwd / 1e12, 2
            )
        except Exception as e:  # noqa: BLE001 — record, keep going
            out[f"{impl}_error"] = str(e)[:120]
    if "flash_fwd_ms" in out and "reference_fwd_ms" in out:
        out["fwd_speedup"] = round(
            out["reference_fwd_ms"] / out["flash_fwd_ms"], 2
        )
        out["fwdbwd_speedup"] = round(
            out["reference_fwdbwd_ms"] / out["flash_fwdbwd_ms"], 2
        )
    print(json.dumps(out), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="one small config (CI smoke)")
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()

    if args.quick or jax.default_backend() == "cpu":
        configs = [(1, 512, 4, 64)]
    else:
        configs = [
            # (batch, seq, heads, head_dim)
            (8, 2048, 8, 128),   # the bench.py flagship shape
            (8, 2048, 16, 64),   # GPT2-ish head_dim
            (2, 8192, 8, 128),   # long context
            (1, 16384, 8, 128),  # longer context
        ]
    # per-call dispatch floor: a chained no-op jit loop, one fence at
    # the end. Configs whose kernel time is near this floor are
    # dispatch-bound, not kernel-bound — the floor
    # line lets a reader discount those.
    noop = jax.jit(lambda x: x + 1)
    a = jnp.zeros((8, 128), jnp.float32)
    device_fence(noop(a))
    n = 50
    t0 = time.monotonic()
    for _ in range(n):
        a = noop(a)
    device_fence(a)
    floor_ms = (time.monotonic() - t0) / n * 1e3
    print(
        json.dumps(
            {
                "metric": "dispatch_floor_ms",
                "value": round(floor_ms, 3),
                "backend": jax.default_backend(),
            }
        ),
        flush=True,
    )
    for cfg in configs:
        bench_config(*cfg, iters=args.iters)


if __name__ == "__main__":
    main()
