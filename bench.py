"""Benchmark: steady-state training throughput of the flagship decoder.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Runs on a TPU and fails on anything else: a throughput measured on
another platform is not this benchmark's number. Model size targets
one v5e chip (16 GB HBM): ~350 M params, bf16 compute, remat, flash
attention. vs_baseline reports achieved MFU / 0.40 — the reference
north-star is >=40 % MFU at scale (BASELINE.md), so 1.0 means parity
with that target.

DLROVER_TPU_FORCE_CPU=1 is the explicit CPU mode: the tiny model, a
line that names platform "cpu", MFU 0 — a check that the script still
runs end to end (tests/test_bench_contract.py), never a measurement.
One process: nothing here starts a child, so nothing competes for the
chip this process holds.
"""

import json
import os
import sys
import time

# generation detection + peak table live in utils/prof.py (one copy:
# the profiler's MFU and this bench must agree on the chip)
from dlrover_tpu.utils.platform import FORCE_CPU_ENV, ensure_cpu_if_forced
from dlrover_tpu.utils.prof import PEAK_TFLOPS, detect_tpu_gen


def _bench_checkpoint(state, step_ms: float) -> dict:
    """Measure the two non-throughput north-star axes (BASELINE.md):
    flash-checkpoint save blocking and shm-restore stall, plus a modeled
    goodput estimate.

    The D2H/H2D legs run on a probe slice of the real state and are
    extrapolated linearly to the full state size, which keeps the
    bench's wall clock bounded while still measuring the actual
    staging path. The save-*blocking* number needs no probe — the
    async engine's critical path is an on-device snapshot dispatch,
    which is measured on the full state."""
    import shutil
    import tempfile

    import jax

    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        CheckpointEngine,
    )

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    eng = CheckpointEngine(ckpt_dir, job_name="benchjob")
    out = {}
    try:
        nbytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(state)
        )
        out["ckpt_gb"] = round(nbytes / 1e9, 2)

        # probe: slice every leaf along axis 0 — SAME tree structure
        # and leaf count as the real state (so the engine's per-leaf
        # dispatch cost is faithfully measured) at a fraction of the
        # bytes (so a slow D2H link keeps the bench's wall clock
        # bounded); byte-proportional legs are extrapolated.
        def _slice_frac(frac):
            def _slice(x):
                if getattr(x, "ndim", 0) == 0 or x.shape[0] < 5:
                    return x
                return x[: max(1, int(x.shape[0] * frac))]

            return jax.tree_util.tree_map(_slice, state)

        def _tree_bytes(t):
            return sum(
                x.nbytes for x in jax.tree_util.tree_leaves(t)
            )

        # measure the D2H rate on a small warm-up leg first, then
        # size the real probe so each remaining leg fits its budget
        leg_budget_s = float(
            os.environ.get("BENCH_CKPT_LEG_BUDGET", "90")
        )
        tiny_frac = max(48e6 / nbytes, 1e-3)
        tiny = _slice_frac(tiny_frac)
        t0 = time.monotonic()
        eng.save_to_memory(0, tiny)  # also warms DMA setup
        warm_s = max(time.monotonic() - t0, 1e-9)
        rate = _tree_bytes(tiny) / warm_s  # bytes/s through the engine
        probe_frac = min(
            0.2,
            max(tiny_frac, rate * leg_budget_s / nbytes),
        )
        probe = _slice_frac(probe_frac)
        probe_bytes = _tree_bytes(probe)
        out["ckpt_probe_gb"] = round(probe_bytes / 1e9, 2)
        scale = nbytes / probe_bytes
        if probe_frac > tiny_frac * 1.5:
            # re-warm at the real probe size (segment resize happens
            # here, off the timed legs)
            eng.save_to_memory(0, probe)
        # save blocking: the async engine's critical path (on-device
        # snapshot dispatch; staging rides a background thread). The
        # dispatch cost is per-leaf, not per-byte, so the probe's
        # number IS the full state's number.
        blocks = []
        stage_probe = None
        for i in (1, 2):
            t0 = time.monotonic()
            blocks.append(eng.save_to_memory_async(i, probe))
            eng.wait_for_staging()
            stage_probe = time.monotonic() - t0
        out["save_block_ms"] = round(min(blocks) * 1e3, 1)
        # staging (D2H + shm write) is byte-proportional: extrapolate
        out["stage_full_est_s"] = round(stage_probe * scale, 2)
        out["d2h_gbps"] = round(
            (probe_bytes / 1e9) / max(stage_probe, 1e-9), 3
        )
        # restore stall, MEASURED on the kill-restore path: a FRESH
        # engine (what a respawned trainer process gets — new shm
        # mapping, new meta read, re-attach from the file) loads the
        # staged step and device_puts it onto the training shardings.
        # This is the wall clock a real recovery pays after respawn.
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            CheckpointEngine as _Eng,
            restore_to_shardings,
        )

        eng2 = _Eng(ckpt_dir, job_name="benchjob")
        try:
            # the two restore legs timed apart: the shm read is host
            # memcpy, the H2D leg rides the chip's host link
            t0 = time.monotonic()
            step, host_state = eng2.load_from_memory(target=probe)
            shm_read_s = max(time.monotonic() - t0, 1e-9)
            t0 = time.monotonic()
            restored = restore_to_shardings(host_state, probe)
            from dlrover_tpu.utils.prof import device_fence

            device_fence(restored)
            h2d_s = time.monotonic() - t0
            # the fence itself costs one round trip per leaf (plus
            # first-use gather compiles) — measure it on the now-
            # complete tree and subtract, or the per-leaf cost gets
            # multiplied by `scale` into the full-state estimate
            t1 = time.monotonic()
            device_fence(restored)
            h2d_s = max(h2d_s - (time.monotonic() - t1), 1e-9)
            restore_probe = shm_read_s + h2d_s
        finally:
            eng2.close()  # client-only: eng owns the IPC server
        out["restore_stall_measured_s"] = round(restore_probe, 2)
        out["restore_shm_read_s"] = round(shm_read_s, 3)
        out["restore_h2d_s"] = round(h2d_s, 3)
        out["restore_measured_gb"] = out["ckpt_probe_gb"]
        out["restore_stall_full_est_s"] = round(
            restore_probe * scale, 2
        )
        out["ckpt_roundtrip_ok"] = bool(
            step == 2 and restored is not None
        )
        # goodput: measured save-blocking + measured restore stall
        # (scaled to the full state by measured byte rate); only MTBF
        # and respawn remain modeled (reference README.md:56-57
        # claims 95% with the same shape of accounting)
        interval_s = 10 * step_ms / 1e3
        mtbf_s = 3600.0
        respawn_s = 20.0
        ckpt_frac = min(blocks) / (interval_s + min(blocks))
        per_failure = (
            restore_probe * scale + respawn_s + interval_s / 2
        )
        goodput = (1.0 - ckpt_frac) * mtbf_s / (mtbf_s + per_failure)
        out["goodput_pct"] = round(goodput * 100, 2)
        out["goodput_assumptions"] = (
            "ckpt@10steps; stall measured (fresh-engine restore, "
            "byte-scaled to full state); modeled: MTBF 1h, respawn 20s"
        )
    except Exception as e:  # noqa: BLE001
        out["ckpt_error"] = str(e)[:200]
    finally:
        try:
            eng.close()
        except Exception:  # noqa: BLE001
            pass
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return out


def main():
    cpu_smoke = os.environ.get(FORCE_CPU_ENV) == "1"
    ensure_cpu_if_forced()

    # pure-AST, no jax: a number benched off a tree that breaks the
    # serving invariants measures the bug, not the system
    from dlrover_tpu.analysis import bench_preflight

    bench_preflight("bench.py")

    # persistent compile cache: an earlier run of this bench primes
    # it, so a later one compiles in seconds
    from dlrover_tpu.runtime import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec

    n_dev = jax.local_device_count()
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and not cpu_smoke:
        sys.exit(
            f"bench.py measures a TPU and found platform {platform!r}; "
            f"set {FORCE_CPU_ENV}=1 for the explicit CPU smoke mode"
        )
    if on_tpu:
        # head_dim 128 (Llama-2's own head size) fills all 128 MXU lanes
        # in the flash kernel; "proj" remat saves the [B,S,dim]-sized
        # projection outputs and recomputes only the mlp-wide matmuls +
        # flash fwd — measured best on v5e (0.56 MFU vs 0.27 in r2).
        # r3 sweep on the real chip: batch 12 → 0.532, batch 16 /
        # remat off / "dots" / "proj_mlp" → compile OOM, XLA reference
        # attention → 0.287. batch 8 + "proj" + flash is the optimum of
        # the explored space.
        # BENCH_REMAT / BENCH_BATCH let the chip session A/B the
        # flagship config (e.g. remat-off at batch 8, the unfired r4
        # lever) without editing this file mid-run; defaults are the
        # measured optimum of the explored space (r3/r4 sweeps).
        remat_policy = os.environ.get("BENCH_REMAT", "proj")
        cfg = llama.LlamaConfig(
            vocab_size=32000,
            dim=1024,
            n_layers=24,
            n_heads=8,
            n_kv_heads=8,
            mlp_dim=4096,
            max_seq_len=2048,
            remat=remat_policy not in ("none", "off"),
            remat_policy=(
                remat_policy
                if remat_policy not in ("none", "off")
                else "full"
            ),
            attn_impl="auto",
        )
        batch_size = int(os.environ.get("BENCH_BATCH", "8"))
        seq_len = 2048
        warmup, iters = 3, 10
    else:  # the explicit CPU smoke mode
        cfg = llama.LlamaConfig.tiny()
        batch_size, seq_len = 4, 64
        warmup, iters = 1, 3

    acc = accelerate(
        init_params=lambda k: llama.init_params(cfg, k),
        loss_fn=lambda p, b, m: llama.loss_fn(cfg, p, b, mesh=m),
        rules=llama.partition_rules(cfg),
        optimizer=optax.adamw(1e-4),
        strategy=Strategy(mesh=MeshSpec.fit(n_dev)),
    )
    state = acc.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, seq_len + 1), 0,
        cfg.vocab_size,
    )
    batch = acc.shard_batch({"tokens": tokens})

    def _sync(metrics):
        # fetch a real scalar: forces completion of the whole dependent
        # step chain
        return float(jax.device_get(metrics["loss"]))

    for _ in range(warmup):
        state, metrics = acc.train_step(state, batch)
    _sync(metrics)

    t0 = time.monotonic()
    for _ in range(iters):
        state, metrics = acc.train_step(state, batch)
    final_loss = _sync(metrics)
    elapsed = time.monotonic() - t0

    tokens_per_step = batch_size * seq_len
    tok_per_sec = tokens_per_step * iters / elapsed
    tok_per_sec_per_chip = tok_per_sec / n_dev

    # headline = causal-accounted FLOPs (what the causal flash kernel
    # actually computes); PaLM-style full-attention accounting reported
    # alongside in detail (the headline must ride
    # the conservative convention, not the ~9%-flattering one)
    flops_causal = llama.flops_per_token(cfg, seq_len, causal=True)
    flops_palm = llama.flops_per_token(cfg, seq_len, causal=False)
    # a device that is not in the peak table is an error
    # (detect_tpu_gen raises); the CPU smoke mode states no MFU
    gen = detect_tpu_gen() if on_tpu else platform
    mfu = mfu_palm = 0.0
    if on_tpu:
        peak = PEAK_TFLOPS[gen]
        mfu = tok_per_sec_per_chip * flops_causal / 1e12 / peak
        mfu_palm = tok_per_sec_per_chip * flops_palm / 1e12 / peak
    suspect = on_tpu and mfu_palm > 1.0  # >100% of peak = broken timing

    # ---- weight-byte accounting (int8 weight-quant PR headline) ----
    # tok/s normalized by resident weight GB: the decode-side
    # quantization work moves THIS ratio, so both benches record it
    # for cross-run comparison (serve_bench phase 17 is the paired
    # int8-vs-f32 measurement)
    _params = getattr(state, "params", state)
    weight_bytes = sum(
        leaf.size * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(_params)
    )
    tok_per_weight_gb = (
        tok_per_sec / (weight_bytes / 1e9) if weight_bytes else 0.0
    )

    # ---- checkpoint axes (reference: flash_checkpoint.md 362-408) ----
    # save-blocking ms of the async shm staging, restore stall from shm,
    # and a goodput estimate from those + the measured step time.
    ckpt = _bench_checkpoint(state, step_ms=elapsed / iters * 1e3)

    print(
        json.dumps(
            {
                "metric": "tokens_per_sec_per_chip",
                "value": round(tok_per_sec_per_chip, 1),
                "unit": "tok/s/chip",
                "vs_baseline": round(mfu / 0.40, 4) if on_tpu else 0.0,
                "detail": {
                    "model_params_m": round(
                        llama.num_params(cfg) / 1e6, 1
                    ),
                    "mfu": round(mfu, 4),
                    "mfu_palm": round(mfu_palm, 4),
                    "mfu_convention": (
                        "headline mfu/vs_baseline are causal-"
                        "accounted (only the lower-triangular "
                        "attention blocks the kernel computes are "
                        "credited); mfu_palm credits the full "
                        "S x S score matrix, ~9% higher at seq 2048"
                    ),
                    "chip": gen,
                    "device": {
                        "platform": platform,
                        "kind": jax.devices()[0].device_kind,
                        "count": len(jax.devices()),
                    },
                    "n_devices": n_dev,
                    "config": {
                        "batch": batch_size,
                        "seq": seq_len,
                        "remat": (
                            cfg.remat_policy if cfg.remat else "none"
                        ),
                        "attn": cfg.attn_impl,
                    },
                    "step_ms": round(elapsed / iters * 1e3, 1),
                    "loss": final_loss,
                    "suspect_timing": suspect,
                    "weight_bytes_device": int(weight_bytes),
                    "tok_per_sec_per_weight_gb": round(
                        tok_per_weight_gb, 1
                    ),
                    **ckpt,
                },
            }
        )
    )


if __name__ == "__main__":
    main()
