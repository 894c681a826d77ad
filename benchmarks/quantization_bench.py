"""Quantization kernel microbench: Pallas int8 quantize/dequantize
throughput + the byte-savings arithmetic of the quantized collectives.

The reference ships 4.6k LoC of CUDA for exactly this
(atorch/ops/csrc/quantization/{quantize.cu,dequantize.cu,
quant_reduce.cu}) because gradient compression halves/quarters the
fabric bytes of ZeRO reductions. On TPU the collectives are XLA/ICI,
but the quantize/dequantize kernels still gate whether compression is
*worth it*: they must run well above the ICI feed rate or they become
the bottleneck they were meant to remove.

Measures on whatever backend is live (single chip):
  - quantize_int8 / dequantize_int8 GB/s across sizes
  - quantize->dequantize round-trip error (sanity, printed not timed)
  - the single-chip shard_map path of quantized_all_reduce_tree (on
    one device the gather is local, so this times the quantize_any +
    all_gather + dequant-sum program shape, not the wire; the ring
    reduce-scatter's ppermute hops need >1 chip and are covered by
    the 8-device CPU-mesh tests)

Run:  python benchmarks/quantization_bench.py   (CPU: interpret mode,
smoke only — Pallas interpret is orders slower and not reported as
throughput). One JSON line per measurement.
"""

import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.utils.platform import ensure_cpu_if_forced  # noqa: E402

ensure_cpu_if_forced()


def main():
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import quantization as q
    from dlrover_tpu.utils.prof import timed_with_fence

    on_tpu = jax.default_backend() not in ("cpu",)
    sizes_mb = [16, 64, 256] if on_tpu else [1]

    for mb in sizes_mb:
        n = mb * 1024 * 1024 // 4  # f32 elements
        x = jax.random.normal(
            jax.random.PRNGKey(0), (n // 1024, 1024), jnp.float32
        )  # kernels take [m, n] blocks
        qfn = jax.jit(lambda x: q.quantize_int8(x))
        qx, s = qfn(x)  # compile
        dfn = jax.jit(
            lambda qx, s: q.dequantize_int8(qx, s, out_dtype=jnp.float32)
        )
        y = dfn(qx, s)

        row = {
            "metric": "quant.int8",
            "size_mb": mb,
            "backend": jax.default_backend(),
        }
        if on_tpu:
            # single-call timing is bound by the dispatch + fence
            # floor, not the kernel, at these sizes.
            # Time a DATA-DEPENDENT quantize→dequantize chain inside
            # one jit instead: K1 vs K2 chain lengths difference
            # isolates per-roundtrip kernel time with dispatch
            # amortized out.
            def chain(k):
                def run(x0):
                    def body(_, xc):
                        qx, sx = q.quantize_int8(xc)
                        return q.dequantize_int8(
                            qx, sx, out_dtype=jnp.float32
                        )

                    return jax.lax.fori_loop(0, k, body, x0)

                return jax.jit(run)

            c2, c10 = chain(2), chain(10)
            t2, _ = timed_with_fence(lambda: c2(x), iters=3)
            t10, _ = timed_with_fence(lambda: c10(x), iters=3)
            rt = max((t10 - t2) / 8, 1e-9)  # s per q+dq roundtrip
            row["roundtrip_ms"] = round(rt * 1e3, 3)
            # bytes moved per roundtrip: read f32 + write int8+scales
            # + read int8+scales + write f32 ≈ 2.5x the f32 size
            row["roundtrip_eff_gbps"] = round(
                2.5 * mb / 1024 / rt, 1
            )
        err = float(
            jnp.max(jnp.abs(y - x)) / (jnp.max(jnp.abs(x)) + 1e-9)
        )
        row["roundtrip_max_rel_err"] = round(err, 5)
        print(json.dumps(row), flush=True)

    # the one-shot all-reduce tree on a 1-device mesh: the gather is
    # local, so this times the quantize_any + all_gather + dequant-sum
    # program shape (the ring reduce-scatter's ppermute hops need >1
    # chip; CPU-mesh tests cover them)
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    # leaves carry a leading per-rank axis of size n (= mesh size 1)
    g = jax.random.normal(
        jax.random.PRNGKey(1), (1, 4 * 1024 * 1024), jnp.float32
    )  # 16 MB
    ar = jax.jit(
        lambda g: q.quantized_all_reduce_tree(
            g, mesh=mesh, axis_name="x"
        )
    )
    try:
        out = ar(g)
        row = {
            "metric": "quant.all_reduce_1dev",
            "size_mb": 16,
            "backend": jax.default_backend(),
        }
        if on_tpu:
            t, _ = timed_with_fence(lambda: ar(g), iters=10)
            row["ms"] = round(t * 1e3, 3)
            row["gbps"] = round(16 / 1024 / t, 2)
        rel = float(
            jnp.max(jnp.abs(out - g[0])) / (jnp.max(jnp.abs(g)) + 1e-9)
        )
        row["vs_uncompressed_max_rel_err"] = round(rel, 5)
        print(json.dumps(row), flush=True)
    except Exception as e:  # noqa: BLE001 — record, keep going
        print(
            json.dumps(
                {"metric": "quant.all_reduce_1dev", "error": str(e)[:160]}
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
