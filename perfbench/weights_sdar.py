"""The weights of the SDAR configuration, made by the benchmark from
--seed on the device in one jitted call, in the type they are used in,
and handed to both the program and the plain reference
(reference_sdar.py): neither makes weights of its own.

Layout (what dlrover_tpu/models/llama.py reads for a model with
experts and normalised q and k; layer weights stacked on a leading
axis):
  embed/weight [V, D]        lm_head/weight [D, V]
  final_norm/scale [D]
  layers/{attn_norm, mlp_norm} [L, D]
  layers/{wq [L, D, H*hd], wk, wv [L, D, KV*hd], wo [L, H*hd, D]}
  layers/{q_norm, k_norm} [L, hd]
  layers/router [L, D, E]
  layers/{we_gate, we_up [L, E, D, M], we_down [L, E, M, D]}

The layers' norm scales 1, embedding N(0, 0.02), every matrix N(0,
1/fan_in), the router too (weights_mellum2.py says why). The scales of
q's and k's norms are 1 + N(0, 0.1): at 1 a norm left out of one side,
or applied after the rotary turn, would change nothing that is
compared.
"""

import functools
import math

import weights as base

hashable = base.hashable
seed_key = base.seed_key


def shapes(model: dict) -> dict:
    L, D = model["num_hidden_layers"], model["hidden_size"]
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    E, M, V = (
        model["num_experts"], model["moe_intermediate_size"],
        model["vocab_size"],
    )
    return {
        "embed": {"weight": (V, D)},
        "layers": {
            "attn_norm": (L, D),
            "wq": (L, D, H * hd), "wk": (L, D, KV * hd),
            "wv": (L, D, KV * hd), "wo": (L, H * hd, D),
            "q_norm": (L, hd), "k_norm": (L, hd),
            "mlp_norm": (L, D),
            "router": (L, D, E),
            "we_gate": (L, E, D, M), "we_up": (L, E, D, M),
            "we_down": (L, E, M, D),
        },
        "final_norm": {"scale": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def init_params(model: dict, key, dtype):
    """Traced under jit by callers. The experts' stacks (1.2 GB a
    leaf at 6 layers) are drawn a layer at a time, so that the
    generator's temporaries are a layer's and not the stack's."""
    import jax
    import jax.numpy as jnp

    tree = shapes(model)
    flat = [
        (group, name, shape)
        for group, leaves in tree.items() for name, shape in leaves.items()
    ]
    keys = jax.random.split(key, len(flat))
    out = {group: {} for group in tree}
    for k, (group, name, shape) in zip(keys, flat):
        if name in ("q_norm", "k_norm"):
            out[group][name] = (
                1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            ).astype(dtype)
            continue
        if name.endswith("_norm") or name == "scale":
            out[group][name] = jnp.ones(shape, dtype)
            continue
        scale = jnp.asarray(
            0.02 if group == "embed" else 1.0 / math.sqrt(shape[-2]), dtype
        )
        if len(shape) == 4:
            out[group][name] = jax.lax.map(
                lambda kk, s=shape, c=scale: jax.random.normal(
                    kk, s[1:], dtype) * c,
                jax.random.split(k, shape[0]),
            )
        else:
            out[group][name] = jax.random.normal(k, shape, dtype) * scale
    return out


def _hashable(model: dict) -> tuple:
    return hashable({
        k: v for k, v in model.items()
        if k in ("num_hidden_layers", "hidden_size", "num_attention_heads",
                 "num_key_value_heads", "head_dim", "num_experts",
                 "moe_intermediate_size", "vocab_size")
    })


@functools.lru_cache(maxsize=None)
def _maker():
    import jax
    import jax.numpy as jnp

    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    return jax.jit(
        lambda items, key, dtype: init_params(dict(items), key, dtypes[dtype]),
        static_argnums=(0, 2),
    )


def make_params(model: dict, seed: int, dtype: str):
    """The weights of `seed` on the device, in one jitted call (the
    key is an argument: one program serves every seed)."""
    return _maker()(_hashable(model), seed_key(seed), dtype)


def tiny_model(model: dict) -> dict:
    """The rehearsal's sizes under the same keys: every mechanism
    kept (head_dim beside hidden / heads, normalised q and k, top-k of
    a softmax, the block of 4 and a mask id, the vocabulary's last)."""
    return dict(
        model, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=256,
        num_hidden_layers=2, rope_theta=10000.0,
        generation=dict(model["generation"], mask_token_id=255),
    )
