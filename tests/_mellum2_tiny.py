"""A Mellum2-shaped model at a test's size: the program's config, the
reference's `model` dict (tests/reference_models/mellum2.py) and one
tree of seeded random weights both read. Every mechanism is there:
head_dim beside dim / n_heads, window, window, window, full to a
period, YaRN on the full layers, experts routed top-k of a softmax."""

import math

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import LlamaConfig, RopeSpec
from reference_models import mellum2 as ref


def model_dict(n_layers=4, window=8, **over):
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    model = {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32,
        "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "norm_topk_prob": True,
        "vocab_size": 128, "num_hidden_layers": n_layers,
        "layer_types": kinds * 7, "sliding_window": window,
        "rms_norm_eps": 1e-6,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                "original_max_position_embeddings": 16,
                "beta_fast": 32, "beta_slow": 1,
                "attention_factor": 0.1 * math.log(4.0) + 1.0,
            },
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000.0,
            },
        },
    }
    model.update(over)
    return model


def config(model, dtype=jnp.float32, **over) -> LlamaConfig:
    """The program's config of a `model` dict (the mapping the
    benchmark's driver makes for the published file)."""
    rope = model["rope_parameters"]
    full, win = rope["full_attention"], rope["sliding_attention"]
    kw = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"],
        mlp_dim=model["moe_intermediate_size"],
        n_experts=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"], moe_routing="dropless",
        layer_pattern=("window", "window", "window", "full"),
        sliding_window=model["sliding_window"],
        rope_theta=float(win["rope_theta"]),
        rope_full=RopeSpec(
            theta=float(full["rope_theta"]),
            yarn_factor=float(full["factor"]),
            original_len=full["original_max_position_embeddings"],
            beta_fast=full["beta_fast"], beta_slow=full["beta_slow"],
            attention_factor=full["attention_factor"],
        ),
        rope_window=RopeSpec(theta=float(win["rope_theta"])),
        norm_eps=model["rms_norm_eps"], max_seq_len=256,
        dtype=dtype, param_dtype=dtype, remat=False,
        attn_impl="auto",
    )
    kw.update(over)
    return LlamaConfig(**kw)


def params(model, seed=0, dtype=jnp.float32):
    """Norm scales 1, embedding N(0, 0.02), matrices N(0, 1/fan_in),
    the router too (a normed token has unit RMS, so its logits have
    unit spread)."""
    tree = ref.shapes(model)
    flat = [
        (g, n, shape) for g, leaves in tree.items()
        for n, shape in leaves.items()
    ]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = {g: {} for g in tree}
    for key, (g, n, shape) in zip(keys, flat):
        if n.endswith("_norm") or n == "scale":
            out[g][n] = jnp.ones(shape, dtype)
        elif g == "embed":
            out[g][n] = (jax.random.normal(key, shape) * 0.02).astype(dtype)
        else:
            w = jax.random.normal(key, shape) / math.sqrt(shape[-2])
            out[g][n] = w.astype(dtype)
    return out


def published_model(n_layers=28) -> dict:
    """Mellum2-12B-A2.5B-Instruct's config.json numbers (the catalog
    row beside the model-configs guide), at a depth of `n_layers`."""
    return model_dict(
        n_layers=n_layers, window=1024,
        hidden_size=2304, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, num_experts=64, num_experts_per_tok=8,
        moe_intermediate_size=896, vocab_size=98304,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192,
                "beta_fast": 32, "beta_slow": 1,
                "attention_factor": 1.2772588722239782,
            },
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 500000,
            },
        },
    )
