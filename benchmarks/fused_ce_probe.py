"""Fused cross-entropy on-TPU probe (never yet timed on hardware).

The chunked fused CE (ops/fused_ce.py, opt-in via LlamaConfig.fused_ce)
never materializes the [B,S,V] logits; r3's sweep showed batch 16 OOMs
at compile WITHOUT it. This times the flagship bench config at batch 8
fused vs unfused, then tries batch 16 fused — if that compiles and
beats batch 8 tokens/s, bench.py's config should flip.

Run: python benchmarks/fused_ce_probe.py   (CPU smoke: tiny shapes)
One JSON line per config; a config that fails (OOM) reports the error.
"""

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.utils.platform import ensure_cpu_if_forced  # noqa: E402

ensure_cpu_if_forced()


def main():
    import jax
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec

    on_tpu = jax.default_backend() not in ("cpu",)
    n_dev = jax.local_device_count()

    def cfg_for(fused):
        if on_tpu:
            return llama.LlamaConfig(
                vocab_size=32000, dim=1024, n_layers=24, n_heads=8,
                n_kv_heads=8, mlp_dim=4096, max_seq_len=2048,
                remat=True, remat_policy="proj", attn_impl="auto",
                fused_ce=fused,
            )
        return llama.LlamaConfig.tiny(fused_ce=fused)

    seq = 2048 if on_tpu else 64
    warmup, iters = (3, 10) if on_tpu else (1, 2)
    configs = (
        [("b8_unfused", 8, False), ("b8_fused", 8, True),
         ("b12_fused", 12, True), ("b16_fused", 16, True)]
        if on_tpu
        else [("b4_unfused", 4, False), ("b4_fused", 4, True)]
    )

    for name, batch, fused in configs:
        row = {"metric": f"fused_ce.{name}", "unit": "tok/s/chip",
               "batch": batch, "fused": fused,
               "backend": jax.default_backend()}
        try:
            cfg = cfg_for(fused)
            acc = accelerate(
                init_params=lambda k, c=cfg: llama.init_params(c, k),
                loss_fn=lambda p, b, m, c=cfg: llama.loss_fn(
                    c, p, b, mesh=m
                ),
                rules=llama.partition_rules(cfg),
                optimizer=optax.adamw(1e-4),
                strategy=Strategy(mesh=MeshSpec.fit(n_dev)),
            )
            state = acc.init(jax.random.PRNGKey(0))
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (batch, seq + 1), 0,
                cfg.vocab_size,
            )
            b = acc.shard_batch({"tokens": tokens})
            t_c0 = time.monotonic()
            for _ in range(warmup):
                state, m = acc.train_step(state, b)
            float(jax.device_get(m["loss"]))
            row["compile_plus_warmup_s"] = round(
                time.monotonic() - t_c0, 1
            )
            t0 = time.monotonic()
            for _ in range(iters):
                state, m = acc.train_step(state, b)
            float(jax.device_get(m["loss"]))
            dt = time.monotonic() - t0
            row["value"] = round(batch * seq * iters / dt / n_dev, 1)
            row["step_ms"] = round(dt / iters * 1e3, 1)
            # free before the next (bigger) config compiles
            del state, acc, b
        except Exception as e:  # noqa: BLE001 — OOM is a RESULT here
            row["value"] = 0.0
            row["error"] = str(e)[:160]
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
