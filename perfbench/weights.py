"""The weights every cell runs on, made by the benchmark from --seed
on the device in one jitted call, in the type they are used in. The
program under test and the plain reference are both handed this tree;
neither makes weights of its own, so neither can lean on the other's.

Layout (the Llama-family checkpoint layout the program's models/llama.py
reads; layer weights are stacked on a leading axis):
  embed/weight [V, D]        lm_head/weight [D, V]
  final_norm/scale [D]
  layers/{attn_norm, mlp_norm} [L, D]
  layers/{wq [L, D, H*hd], wk, wv [L, D, KV*hd], wo [L, H*hd, D]}
  layers/{w_gate, w_up [L, D, M], w_down [L, M, D]}
"""

import functools
import math


def shapes(model: dict) -> dict:
    L, D, M = (
        model["num_hidden_layers"], model["hidden_size"],
        model["intermediate_size"],
    )
    V = model["vocab_size"]
    hd = model["head_dim"]
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    return {
        "embed": {"weight": (V, D)},
        "layers": {
            "attn_norm": (L, D),
            "wq": (L, D, H * hd), "wk": (L, D, KV * hd),
            "wv": (L, D, KV * hd), "wo": (L, H * hd, D),
            "mlp_norm": (L, D),
            "w_gate": (L, D, M), "w_up": (L, D, M), "w_down": (L, M, D),
        },
        "final_norm": {"scale": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def init_params(model: dict, key, dtype):
    """Norm scales 1, embedding N(0, 0.02), every matrix N(0, 1/fan_in)
    (fan_in is the second-to-last axis). Traced under jit by callers."""
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
        elif group == "embed":
            out[group][name] = jax.random.normal(k, shape, dtype) * jnp.asarray(
                0.02, dtype
            )
        else:
            # drawn in the target type: a float32 draw of a stacked
            # bf16 leaf would be a temporary twice the leaf's size
            out[group][name] = jax.random.normal(k, shape, dtype) * jnp.asarray(
                1.0 / math.sqrt(shape[-2]), dtype
            )
    return out


def hashable(model: dict) -> tuple:
    """The configuration's plain values, as a key jit can hash."""
    return tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, bool, type(None)))
    ))


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
    """The weights of `seed` on the device, in one jitted call. The
    key is an ARGUMENT of the program: closed over, it would be a
    constant of the program, and every seed would compile anew."""
    return _maker()(hashable(model), seed_key(seed), dtype)


def tiny_model(model: dict) -> dict:
    """The rehearsal's sizes (LlamaConfig.tiny's) under the same keys."""
    return dict(
        model, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        vocab_size=256,
    )


def seed_key(seed: int):
    """A PRNG key from a seed of up to 63 bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )
