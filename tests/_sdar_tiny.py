"""An SDAR-shaped model at a test's size: the program's config, the
reference's `model` dict (tests/reference_models/sdar.py) and one tree
of seeded random weights both read. Every mechanism is there: a head
width beside dim / n_heads, per-head norms of q and k with scales off
1, experts routed top-k of a softmax, a block length and a mask id."""

import math

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import LlamaConfig
from reference_models import sdar as ref

BLOCK = 4


def model_dict(n_layers=2, **over):
    model = {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32,
        "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "norm_topk_prob": True,
        "vocab_size": 128, "num_hidden_layers": n_layers,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": False,
    }
    model.update(over)
    return model


def mask_id(model) -> int:
    """The tiny model's mask id: the vocabulary's last (prompts draw
    below it)."""
    return model["vocab_size"] - 1


def config(model, dtype=jnp.float32, block=BLOCK, **over) -> LlamaConfig:
    """The program's config of a `model` dict (the mapping the
    benchmark's driver makes for the published file)."""
    kw = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"],
        mlp_dim=model["moe_intermediate_size"],
        n_experts=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"], moe_routing="dropless",
        qk_norm=True, block_length=block, mask_token_id=mask_id(model),
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], max_seq_len=256,
        dtype=dtype, param_dtype=dtype, remat=False,
        tie_embeddings=bool(model["tie_word_embeddings"]),
        attn_impl="auto",
    )
    kw.update(over)
    return LlamaConfig(**kw)


def params(model, seed=0, dtype=jnp.float32):
    """Embedding N(0, 0.02), matrices N(0, 1/fan_in), the router too;
    the layers' norm scales 1, but q's and k's 1 + N(0, 0.1): a scale
    of 1 would hide a norm left out of one side."""
    tree = ref.shapes(model)
    flat = [
        (g, n, shape) for g, leaves in tree.items()
        for n, shape in leaves.items()
    ]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = {g: {} for g in tree}
    for key, (g, n, shape) in zip(keys, flat):
        if n in ("q_norm", "k_norm"):
            out[g][n] = (1.0 + 0.1 * jax.random.normal(key, shape)).astype(
                dtype)
        elif n.endswith("_norm") or n == "scale":
            out[g][n] = jnp.ones(shape, dtype)
        elif g == "embed":
            out[g][n] = (jax.random.normal(key, shape) * 0.02).astype(dtype)
        else:
            w = jax.random.normal(key, shape) / math.sqrt(shape[-2])
            out[g][n] = w.astype(dtype)
    return out


def published_model(n_layers=48) -> dict:
    """SDAR-30B-A3B-Chat's config.json numbers (the catalog row beside
    the model-configs guide), at a depth of `n_layers`."""
    return model_dict(
        n_layers=n_layers, hidden_size=2048, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128, num_experts=128,
        num_experts_per_tok=8, moe_intermediate_size=768,
        vocab_size=151936, rope_theta=1000000,
    )


def published_config(n_layers=6, **over) -> LlamaConfig:
    """The program's config at the published widths, bf16, the
    family's block of 4 and mask id 151669."""
    return config(
        published_model(n_layers), dtype=jnp.bfloat16,
        mask_token_id=151669, max_seq_len=2048, **over,
    )
