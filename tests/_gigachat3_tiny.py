"""A GigaChat3-shaped model at a test's size: the reference's `model`
dict (tests/reference_models/gigachat3.py), the program's config of
it, one tree of seeded random weights in the published layout, and
that tree as the program reads it. Every mechanism is there: latent
attention with its five sizes and YaRN's mscale, one leading dense
layer of another width, a sigmoid router with a choice-only bias and
groups, a shared expert, a held share of the routed experts."""

import math

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import LlamaConfig, RopeSpec
from reference_models import gigachat3 as ref


def model_dict(n_layers=3, **over):
    model = {
        "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 8, "q_lora_rank": 48, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24,
        "intermediate_size": 160, "moe_intermediate_size": 32,
        "n_routed_experts": 32, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "n_group": 4, "topk_group": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "first_k_dense_replace": 1, "num_hidden_layers": n_layers,
        "vocab_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "rope_scaling": {
            "rope_type": "yarn", "factor": 4.0,
            "original_max_position_embeddings": 16,
            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
            "mscale_all_dim": 1,
        },
    }
    model.update(over)
    return model


def config(model, held=None, dtype=jnp.float32, **over) -> LlamaConfig:
    """The program's config of a `model` dict (the mapping the
    benchmark's driver makes for the published file); `held` =
    (first, count) of the routed experts held here."""
    rs = model["rope_scaling"]

    def m(scale):
        return 0.1 * scale * math.log(rs["factor"]) + 1.0

    kw = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        first_k_dense=model["first_k_dense_replace"],
        dense_mlp_dim=model["intermediate_size"],
        mlp_dim=model["moe_intermediate_size"],
        n_experts=model["n_routed_experts"],
        moe_top_k=model["num_experts_per_tok"], moe_routing="dropless",
        n_shared_experts=model["n_shared_experts"],
        moe_scoring=model["scoring_func"],
        moe_n_group=model["n_group"], moe_topk_group=model["topk_group"],
        moe_routed_scaling=model["routed_scaling_factor"],
        experts_held=tuple(held or ()),
        rope_theta=float(model["rope_theta"]),
        rope_full=RopeSpec(
            theta=float(model["rope_theta"]),
            yarn_factor=float(rs["factor"]),
            original_len=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            attention_factor=m(rs["mscale"]) / m(rs["mscale_all_dim"]),
        ),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=model["rms_norm_eps"], max_seq_len=256,
        dtype=dtype, param_dtype=dtype, remat=False, attn_impl="auto",
    )
    kw.update(over)
    return LlamaConfig(**kw)


def params(model, seed=0, dtype=jnp.float32):
    """The published layout, every routed expert: norm scales 1,
    embedding N(0, 0.02), matrices N(0, 1/fan_in), the router too;
    the router's bias N(0, 0.1), a spread that moves choices."""
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
        elif n == "router_bias":
            out[g][n] = 0.1 * jax.random.normal(key, shape)
        elif g == "embed":
            out[g][n] = (jax.random.normal(key, shape) * 0.02).astype(dtype)
        else:
            w = jax.random.normal(key, shape) / math.sqrt(shape[-2])
            out[g][n] = w.astype(dtype)
    return out


def share(model, tree, held):
    """The published tree with the routed experts of `held` =
    (first, count) only: what the reference is given for one chip's
    share."""
    first, count = held
    out = {g: dict(leaves) for g, leaves in tree.items()}
    for n in ("we_gate", "we_up", "we_down"):
        out["layers"][n] = tree["layers"][n][:, first:first + count]
    return out


def to_program(model, tree):
    """A (shared) published tree as the program reads it: W_kvb's
    columns, a head's [k_nope, v], as the two leaves `wk_b` and
    `wv_b`."""
    H, cr = model["num_attention_heads"], model["kv_lora_rank"]
    nope, vd = model["qk_nope_head_dim"], model["v_head_dim"]
    out = {g: dict(leaves) for g, leaves in tree.items()}
    for g in ("dense_layers", "layers"):
        w = out[g].pop("wkv_b")
        w = w.reshape(w.shape[0], cr, H, nope + vd)
        out[g]["wk_b"] = w[..., :nope].reshape(w.shape[0], cr, H * nope)
        out[g]["wv_b"] = w[..., nope:].reshape(w.shape[0], cr, H * vd)
    return out


def published_model(n_layers=5, first_k_dense=1) -> dict:
    """GigaChat3.1-702B-A36B's config.json numbers (the catalog row
    beside the model-configs guide), at the benchmark's depth."""
    return model_dict(
        n_layers=n_layers, first_k_dense_replace=first_k_dense,
        hidden_size=7168, num_attention_heads=64, num_key_value_heads=64,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=192, intermediate_size=18432,
        moe_intermediate_size=2048, n_routed_experts=256,
        num_experts_per_tok=8, n_group=8, topk_group=4,
        vocab_size=16032, rope_theta=100000,
        rope_scaling={
            "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
            "mscale_all_dim": 1,
        },
    )
