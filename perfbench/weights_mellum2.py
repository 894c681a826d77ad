"""The weights of the Mellum2 configuration, made by the benchmark
from --seed on the device in one jitted call, in the type they are
used in, and handed to both the program and the plain reference
(reference_mellum2.py): neither makes weights of its own.

Layout (what dlrover_tpu/models/llama.py reads for a model with
experts; layer weights stacked on a leading axis):
  embed/weight [V, D]        lm_head/weight [D, V]
  final_norm/scale [D]
  layers/{attn_norm, mlp_norm} [L, D]
  layers/{wq [L, D, H*hd], wk, wv [L, D, KV*hd], wo [L, H*hd, D]}
  layers/router [L, D, E]
  layers/{we_gate, we_up [L, E, D, M], we_down [L, E, M, D]}

Norm scales 1, embedding N(0, 0.02), every matrix N(0, 1/fan_in).
The router too: a normed token has unit RMS, so its 64 logits have
unit spread, and the 8th and 9th of a softmax's probabilities seldom
lie within bfloat16's rounding of each other (PERF.md, section 2).
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
            "mlp_norm": (L, D),
            "router": (L, D, E),
            "we_gate": (L, E, D, M), "we_up": (L, E, D, M),
            "we_down": (L, E, M, D),
        },
        "final_norm": {"scale": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def init_params(model: dict, key, dtype):
    """Traced under jit by callers. The experts' stacks (3.2 GB a
    leaf at 12 layers) are drawn a layer at a time, so that the
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
    kept (head_dim beside hidden / heads, the period of four, a
    window the contexts cross, YaRN, top-k of a softmax)."""
    rope = {
        kind: dict(spec, rope_theta=10000.0)
        for kind, spec in model["rope_parameters"].items()
    }
    rope["full_attention"].update(
        factor=4.0, original_max_position_embeddings=16,
        attention_factor=0.1 * math.log(4.0) + 1.0,
    )
    return dict(
        model, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=256,
        num_hidden_layers=4, sliding_window=16, rope_parameters=rope,
    )
