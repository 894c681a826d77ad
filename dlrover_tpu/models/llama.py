"""Llama-family decoder, written TPU-first.

Reference parity: the reference trains Llama-2 through HF transformers +
ATorch rewrites (atorch/examples/llama2, atorch FA adapters
modules/transformer/layers.py:1353 `LlamaAttentionFA`). Here the model is
a pure-JAX functional transformer designed for pjit/GSPMD:

- layers are STACKED (leading axis = n_layers) and applied with
  `lax.scan` → one compiled layer body, fast compile, natural remat point;
- params live in f32 (optimizer precision), compute casts to bf16 (MXU);
- attention goes through ops.attention (Pallas flash kernel on TPU);
- every weight has a PartitionSpec rule (Megatron-style TP + FSDP axes),
  activations carry sharding constraints on (batch, seq, heads).
"""

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from dlrover_tpu.ops.attention import dot_product_attention
from dlrover_tpu.ops.quantization import QuantizedWeight, matmul_any
from dlrover_tpu.parallel.remat import checkpoint_name
from dlrover_tpu.parallel.sharding import constrain

Params = Dict[str, Any]

# kinds of layer a `layer_pattern` may name: "full" attends every
# earlier position, "window" the last `sliding_window` of them
LAYER_KINDS = ("full", "window")


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """Rotary parameters of one kind of layer. `yarn_factor` 0 is the
    plain form; otherwise the static YaRN form (`yarn_frequencies`):
    applied at every length, cos and sin scaled by
    `attention_factor` (0 = 0.1 ln(factor) + 1)."""

    theta: float = 10000.0
    yarn_factor: float = 0.0
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    mlp_dim: int = 11008
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16          # compute dtype
    param_dtype: Any = jnp.float32     # storage dtype
    remat: bool = True                 # checkpoint each layer in scan
    # what the layer scan keeps for its backward pass. "auto": what
    # the device has room for: `accelerate()` takes the first rung of
    # parallel/remat.py's LADDER ("none", "proj_mlp", "full") whose
    # compiled step fits the chip's memory, again after every
    # rebuild of an elastic job; "full" (recompute everything, least
    # HBM) wherever no one chooses: a bare `loss_fn`, an evaluation,
    # a backend that states no limit. Any remat.resolve_policy name,
    # or remat=False, is obeyed as written: pin one to compare
    # programs, or where the process keeps more on the device beside
    # its step than remat.MARGIN_BYTES
    remat_policy: str = "auto"
    attn_impl: str = "auto"            # auto | flash | reference
    # explicit flash block sizes for tuning sweeps (0 = VMEM-aware auto,
    # ops/flash_attention.auto_blocks). Single-device attention only:
    # the sequence-parallel branch (ring/Ulysses) does its own
    # S/sp chunking and ignores these.
    attn_block_q: int = 0
    attn_block_k: int = 0
    seq_parallel: str = "none"         # none | ring | ulysses
    # chunked fused cross-entropy: never materializes [B,S,V] logits
    # (ops/fused_ce.py). Auto-disabled under sequence parallelism
    # (chunking the seq dim conflicts with a sharded seq axis).
    # Default OFF pending real-TPU timing: the compile/step cost on
    # hardware is not measured; numerics + memory behavior are
    # covered by test_fused_ce.py. Flip on per-config where HBM is the binding
    # constraint.
    fused_ce: bool = False
    tie_embeddings: bool = False
    # MoE (0 experts = dense MLP). Experts shard on the "expert" mesh axis.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # the router (the experts' SwiGLU width is mlp_dim): "capacity"
    # is the GShard training layer (drops tokens over capacity);
    # "dropless" sorts the (token, expert) pairs by expert and
    # multiplies group by group (moe.dropless_moe): softmax before
    # the top-k, no token dropped at any load. Serving only.
    moe_routing: str = "capacity"
    # width of a head where it is not dim // n_heads (0 = that)
    attn_head_dim: int = 0
    # one PERIOD of layer kinds, repeated n_layers / len times
    # (() = every layer "full"); a "window" layer's query i sees keys
    # j with i - sliding_window < j <= i
    layer_pattern: Tuple[str, ...] = ()
    sliding_window: int = 0
    # rotary parameters per kind of layer (None = plain, rope_theta)
    rope_full: Optional[RopeSpec] = None
    rope_window: Optional[RopeSpec] = None
    # latent attention (MLA; kv_lora_rank 0 = plain attention): the
    # query goes through a rank-`q_lora_rank` bottleneck, a token's
    # keys and values are ONE normed vector of `kv_lora_rank` numbers
    # beside one rotary key of `qk_rope_head_dim` shared by all heads;
    # a head's query and key are qk_nope_head_dim + qk_rope_head_dim
    # wide, its value `v_head_dim`. `rope_full` holds the rotary
    # parameters; `rope_mscale_all_dim` (YaRN) multiplies the softmax
    # scale by (0.1 * it * ln(yarn_factor) + 1) squared.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_mscale_all_dim: float = 0.0
    # leading layers whose feed-forward is a dense SwiGLU of width
    # `dense_mlp_dim` (a prologue before the expert layers, under
    # params["dense_layers"]); dropless routing only
    first_k_dense: int = 0
    dense_mlp_dim: int = 0
    # experts every token goes through beside the routed ones (their
    # SwiGLU is n_shared_experts * mlp_dim wide)
    n_shared_experts: int = 0
    # the dropless router: "softmax" then top-k, or "sigmoid" scores
    # with a bias that only the CHOICE sees, experts in `moe_n_group`
    # groups of which the `moe_topk_group` best stay, weights
    # normalised over the chosen and times `moe_routed_scaling`
    moe_scoring: str = "softmax"
    moe_n_group: int = 0
    moe_topk_group: int = 0
    moe_routed_scaling: float = 1.0
    # (first, count): the routed experts THIS chip holds of n_experts
    # (() = all). The router still ranks all n_experts; only the pairs
    # that land on held experts are computed (expert parallelism's
    # share of a layer, without its exchange)
    experts_held: Tuple[int, ...] = ()
    # per-head RMSNorm of q and k (over the head's own numbers, one
    # learned scale each a layer: `q_norm`, `k_norm`) before the rotary
    # turn. Serving only.
    qk_norm: bool = False
    # generation by diffusion over BLOCKS of `block_length` positions
    # (0 = one token a forward): attention is causal across blocks and
    # two-sided inside one (key j is seen by query i iff
    # j // block_length <= i // block_length), a forward runs a whole
    # block and fills in the positions that still hold `mask_token_id`
    # (serving/engine.py's diffusion chunk program). Serving only.
    block_length: int = 0
    mask_token_id: int = 0
    # GPipe microbatch count when the mesh has a live "pipe" axis
    # (0 → default to the pipe degree)
    pipeline_microbatches: int = 0
    # LoRA delta scale (alpha; rank comes from the adapter shape).
    # Only read when adapter leaves are present — `lora.inject`
    # returns a config with this set to match its LoraConfig.
    lora_alpha: float = 16.0

    @property
    def moe(self):
        from dlrover_tpu.models.moe import MoeConfig

        return MoeConfig(
            n_experts=self.n_experts,
            top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
        )

    @property
    def routing(self):
        from dlrover_tpu.models.moe import Routing

        return Routing(
            top_k=self.moe_top_k, scoring=self.moe_scoring,
            n_group=self.moe_n_group, topk_group=self.moe_topk_group,
            scaling=self.moe_routed_scaling, held=self.held,
        )

    @property
    def head_dim(self) -> int:
        if self.latent:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attn_head_dim or self.dim // self.n_heads

    @property
    def latent(self) -> bool:
        """Whether attention keeps one latent vector a token (MLA)."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Numbers a cached token takes a layer: the latent and the
        shared rotary key, padded to whole 128-lane tiles (Mosaic
        copies no page whose last dim is not)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def attn_scale(self) -> float:
        """The softmax scale: head_dim^-0.5, times YaRN's mscale
        squared where the configuration gives `rope_mscale_all_dim`."""
        scale = float(self.head_dim) ** -0.5
        rope = self.rope_full
        if self.rope_mscale_all_dim and rope and rope.yarn_factor > 1:
            m = 0.1 * self.rope_mscale_all_dim * math.log(
                rope.yarn_factor) + 1.0
            scale *= m * m
        return scale

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts held here."""
        return tuple(self.experts_held) or (0, self.n_experts)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def period(self) -> Tuple[str, ...]:
        """The layer kinds of one period; a homogeneous model is a
        period of one full layer."""
        return self.layer_pattern or ("full",)

    @property
    def hybrid(self) -> bool:
        """Whether some layer keeps less than every position."""
        return "window" in self.layer_pattern

    def layers_of(self, kind: str) -> int:
        return (
            self.n_layers // len(self.period)
            * self.period.count(kind)
        )

    def rope_of(self, kind: str) -> RopeSpec:
        spec = self.rope_window if kind == "window" else self.rope_full
        return spec or RopeSpec(theta=self.rope_theta)

    def __post_init__(self):
        bad = [k for k in self.layer_pattern if k not in LAYER_KINDS]
        if bad:
            raise ValueError(
                f"layer_pattern names unknown kinds {bad}; known: "
                f"{LAYER_KINDS}"
            )
        if self.layer_pattern and self.n_layers % len(self.layer_pattern):
            raise ValueError(
                f"n_layers={self.n_layers} is not a whole number of "
                f"periods of {len(self.layer_pattern)} layers"
            )
        if self.hybrid and self.sliding_window < 1:
            raise ValueError(
                "a layer_pattern with window layers needs "
                "sliding_window >= 1"
            )
        if self.moe_routing not in ("capacity", "dropless"):
            raise ValueError(
                f"moe_routing must be 'capacity' or 'dropless', got "
                f"{self.moe_routing!r}"
            )
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_scoring must be 'softmax' or 'sigmoid', got "
                f"{self.moe_scoring!r}"
            )
        dropless = self.n_experts > 0 and self.moe_routing == "dropless"
        shares = [
            name for name, on in (
                ("moe_scoring='sigmoid'", self.moe_scoring != "softmax"),
                ("moe_n_group", self.moe_n_group > 0),
                ("n_shared_experts", self.n_shared_experts > 0),
                ("experts_held", bool(self.experts_held)),
                ("first_k_dense", self.first_k_dense > 0),
            ) if on
        ]
        if shares and not dropless:
            raise ValueError(
                f"{', '.join(shares)} need n_experts > 0 and "
                "moe_routing='dropless'"
            )
        if self.moe_n_group and (
            self.n_experts % self.moe_n_group
            or not 0 < self.moe_topk_group <= self.moe_n_group
        ):
            raise ValueError(
                f"moe_n_group={self.moe_n_group} must divide n_experts="
                f"{self.n_experts}, with 0 < moe_topk_group <= it"
            )
        if self.experts_held:
            first, count = self.experts_held
            if not (0 <= first and 0 < count
                    and first + count <= self.n_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} is no (first, "
                    f"count) inside n_experts={self.n_experts}"
                )
        if self.first_k_dense and not (
            0 < self.first_k_dense < self.n_layers
            and self.dense_mlp_dim > 0 and not self.layer_pattern
        ):
            raise ValueError(
                "first_k_dense needs dense_mlp_dim > 0, fewer dense "
                "layers than n_layers, and no layer_pattern"
            )
        if self.block_length and (
            self.block_length < 2
            or self.block_length & (self.block_length - 1)
            or self.latent or self.layer_pattern
            or not 0 <= self.mask_token_id < self.vocab_size
        ):
            raise ValueError(
                f"block_length={self.block_length} needs a power of two "
                "of at least 2 positions a block (the flash kernel's "
                "mask), a mask_token_id inside the "
                "vocabulary, plain attention and no layer_pattern"
            )
        if self.qk_norm and self.latent:
            raise ValueError(
                "qk_norm is the plain attention block's; latent "
                "attention norms its own bottlenecks"
            )
        if self.latent and not (
            self.q_lora_rank > 0 and self.qk_nope_head_dim > 0
            and self.qk_rope_head_dim > 0 and self.v_head_dim > 0
            and not self.layer_pattern
        ):
            raise ValueError(
                "latent attention needs q_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim, and no layer_pattern"
            )

    # ---- presets (sizes follow the reference's benchmark configs) ----
    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama2_13b(cls, **kw) -> "LlamaConfig":
        return cls(
            dim=5120, n_layers=40, n_heads=40, n_kv_heads=40,
            mlp_dim=13824, **kw,
        )

    @classmethod
    def llama2_70b(cls, **kw) -> "LlamaConfig":
        return cls(
            dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
            mlp_dim=28672, max_seq_len=4096, **kw,
        )

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        """Llama-3 family: GQA (8 kv heads), 128k vocab, theta 500k
        (public architecture; the GQA + large-vocab shape stresses the
        kv-head sharding and the fused-CE path differently than the
        llama2 presets)."""
        defaults = dict(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, mlp_dim=14336, max_seq_len=8192,
            rope_theta=500000.0,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-size model: runs on the 8-device CPU mesh in seconds."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            mlp_dim=128, max_seq_len=128, remat=False,
            attn_impl="reference",
        )
        defaults.update(kw)
        return cls(**defaults)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    """Stacked-layer param pytree. All layer weights have a leading
    n_layers axis consumed by lax.scan."""
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    L, D, M = cfg.n_layers, cfg.dim, cfg.mlp_dim
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pd = cfg.param_dtype

    def norm_init(*shape):
        return jnp.ones(shape, pd)

    def dense_init(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, pd) / math.sqrt(fan_in)
        )

    if cfg.latent or cfg.first_k_dense or cfg.n_shared_experts:
        params = _init_share_params(cfg, k_layers, dense_init, norm_init)
        params["embed"] = {
            "weight": jax.random.normal(
                k_embed, (cfg.vocab_size, D), pd
            ) * 0.02,
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = {
                "weight": dense_init(k_out, (D, cfg.vocab_size), D)
            }
        return params
    ks = jax.random.split(k_layers, 8)
    if cfg.n_experts > 0:
        from dlrover_tpu.models.moe import init_moe_mlp

        mlp_weights = init_moe_mlp(
            ks[7], cfg.moe, D, M, n_layers=L,
            param_dtype=pd,
        )
    else:
        mlp_weights = {
            "w_gate": dense_init(ks[4], (L, D, M), D),
            "w_up": dense_init(ks[5], (L, D, M), D),
            "w_down": dense_init(ks[6], (L, M, D), M),
        }
    params = {
        "embed": {
            "weight": jax.random.normal(
                k_embed, (cfg.vocab_size, D), pd
            ) * 0.02,
        },
        "layers": {
            "attn_norm": norm_init(L, D),
            "wq": dense_init(ks[0], (L, D, H * hd), D),
            "wk": dense_init(ks[1], (L, D, KV * hd), D),
            "wv": dense_init(ks[2], (L, D, KV * hd), D),
            "wo": dense_init(ks[3], (L, H * hd, D), H * hd),
            "mlp_norm": norm_init(L, D),
            **mlp_weights,
        },
        "final_norm": {"scale": norm_init(D)},
    }
    if cfg.qk_norm:
        params["layers"].update(
            q_norm=norm_init(L, hd), k_norm=norm_init(L, hd)
        )
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "weight": dense_init(k_out, (D, cfg.vocab_size), D)
        }
    return params


def _init_share_params(cfg, key, dense_init, norm_init) -> Params:
    """The layers of a model with latent attention, leading dense
    layers, shared experts or a held share of the routed experts:
    `dense_layers` (the first_k_dense leading layers, stacked) and
    `layers` (the expert layers, stacked), each with its own leaves.
    The router's choice-only bias is drawn with a spread that moves
    choices (a zero bias would leave choice and weight the same)."""
    D, M = cfg.dim, cfg.mlp_dim
    H = cfg.n_heads
    n_held = cfg.held[1]

    def attention(keys, L):
        if not cfg.latent:
            KV, hd = cfg.n_kv_heads, cfg.head_dim
            return {
                "wq": dense_init(keys[0], (L, D, H * hd), D),
                "wk": dense_init(keys[1], (L, D, KV * hd), D),
                "wv": dense_init(keys[2], (L, D, KV * hd), D),
                "wo": dense_init(keys[3], (L, H * hd, D), H * hd),
            }
        qr, cr = cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rope, vd = (
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        )
        return {
            "wq_a": dense_init(keys[0], (L, D, qr), D),
            "q_norm": norm_init(L, qr),
            "wq_b": dense_init(keys[1], (L, qr, H * (nope + rope)), qr),
            "wkv_a": dense_init(keys[2], (L, D, cr + rope), D),
            "kv_norm": norm_init(L, cr),
            "wk_b": dense_init(keys[3], (L, cr, H * nope), cr),
            "wv_b": dense_init(keys[4], (L, cr, H * vd), cr),
            "wo": dense_init(keys[5], (L, H * vd, D), H * vd),
        }

    def stack(keys, L, ffn):
        return {
            "attn_norm": norm_init(L, D),
            **attention(keys, L),
            "mlp_norm": norm_init(L, D),
            **ffn,
        }

    k_dense, k_moe = jax.random.split(key)
    out = {}
    L0, L1 = cfg.first_k_dense, cfg.n_moe_layers
    if L0:
        ks = jax.random.split(k_dense, 9)
        W = cfg.dense_mlp_dim
        out["dense_layers"] = stack(ks, L0, {
            "w_gate": dense_init(ks[6], (L0, D, W), D),
            "w_up": dense_init(ks[7], (L0, D, W), D),
            "w_down": dense_init(ks[8], (L0, W, D), W),
        })
    ks = jax.random.split(k_moe, 14)
    ffn = {
        "router": dense_init(ks[6], (L1, D, cfg.n_experts), D),
        "we_gate": dense_init(ks[7], (L1, n_held, D, M), D),
        "we_up": dense_init(ks[8], (L1, n_held, D, M), D),
        "we_down": dense_init(ks[9], (L1, n_held, M, D), M),
    }
    if cfg.moe_scoring == "sigmoid":
        ffn["router_bias"] = 0.1 * jax.random.normal(
            ks[10], (L1, cfg.n_experts), jnp.float32
        )
    if cfg.n_shared_experts:
        S = cfg.n_shared_experts * M
        ffn.update(
            ws_gate=dense_init(ks[11], (L1, D, S), D),
            ws_up=dense_init(ks[12], (L1, D, S), D),
            ws_down=dense_init(ks[13], (L1, S, D), S),
        )
    out["layers"] = stack(ks, L1, ffn)
    out["final_norm"] = {"scale": norm_init(D)}
    return out


def partition_rules(cfg: LlamaConfig):
    """(path_regex, PartitionSpec) — layer weights have leading L axis.

    Megatron-style TP: column-parallel wq/wk/wv/w_gate/w_up shard the
    output dim on "tensor"; row-parallel wo/w_down shard the input dim.
    FSDP shards the other dim. lm_head shards vocab on tensor; the
    EMBEDDING shards D only (vocab replicated in layout) so the token
    gather stays local — see the embed rule's comment below.
    """
    moe_rules = []
    if cfg.n_experts > 0:
        from dlrover_tpu.models.moe import moe_partition_rules

        moe_rules = moe_partition_rules()
    from dlrover_tpu.models.lora import lora_partition_rules

    # adapter rules FIRST: `layers/wq_lora_a` would otherwise match
    # the broader `layers/wq` rule with the wrong axis count
    moe_rules = moe_rules + lora_partition_rules()
    return moe_rules + [
        # D-axis sharding ONLY for the embedding: a vocab-sharded
        # table turns `weight[tokens]` into an involuntary full
        # all-gather of the table every step (SPMD "involuntary full
        # rematerialization", surfaced by the 7B v5p-64 AOT compile).
        # Sharding D over fsdp+tensor keeps per-device bytes identical
        # while the gather stays local; the only comm left is the
        # activation-sized all-gather at the constrain below it.
        (r"embed/weight", P(None, ("fsdp", "tensor"))),
        (r"layers/wq", P("pipe", "fsdp", "tensor")),
        (r"layers/wk", P("pipe", "fsdp", "tensor")),
        (r"layers/wv", P("pipe", "fsdp", "tensor")),
        (r"layers/wo", P("pipe", "tensor", "fsdp")),
        (r"layers/w_gate", P("pipe", "fsdp", "tensor")),
        (r"layers/w_up", P("pipe", "fsdp", "tensor")),
        (r"layers/w_down", P("pipe", "tensor", "fsdp")),
        (r"layers/(attn|mlp)_norm", P("pipe", None)),
        (r"final_norm/scale", P(None)),
        (r"lm_head/weight", P("fsdp", "tensor")),
    ]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * scale.astype(x.dtype)


def yarn_frequencies(spec: RopeSpec, d: int):
    """(inverse frequencies [d/2] as a numpy array, cos/sin factor)
    of one kind of layer. Plain: theta^(-2i/d) and 1. YaRN (static
    form): between the dimension that turns `beta_fast` times over
    `original_len` positions and the one that turns `beta_slow`
    times, a linear ramp blends the plain frequency into the
    frequency divided by `yarn_factor`."""
    half = np.arange(0, d, 2, dtype=np.float64) / d
    freqs = spec.theta ** -half
    if not spec.yarn_factor:
        return freqs.astype(np.float32), 1.0

    def turns_dim(rotations):
        return (
            d * math.log(spec.original_len / (rotations * 2 * math.pi))
            / (2 * math.log(spec.theta))
        )

    lo = max(math.floor(turns_dim(spec.beta_fast)), 0)
    hi = min(math.ceil(turns_dim(spec.beta_slow)), d - 1)
    ramp = np.clip(
        (np.arange(d // 2, dtype=np.float64) - lo) / max(hi - lo, 1e-3),
        0.0, 1.0,
    )
    freqs = freqs * (1 - ramp) + freqs / spec.yarn_factor * ramp
    factor = spec.attention_factor or (
        0.1 * math.log(spec.yarn_factor) + 1.0
    )
    return freqs.astype(np.float32), factor


def _rope(x: jax.Array, positions: jax.Array, theta) -> jax.Array:
    """Rotary embedding on [B, S, H, D]. `theta` is the plain base,
    or a RopeSpec (a kind of layer's own parameters)."""
    d = x.shape[-1]
    factor = 1.0
    if isinstance(theta, RopeSpec):
        freqs, factor = yarn_frequencies(theta, d)
        freqs = jnp.asarray(freqs)
    else:
        freqs = jnp.exp(
            -math.log(theta) * jnp.arange(0, d, 2, dtype=jnp.float32) / d
        )
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _compute_weights(cfg: LlamaConfig, layer_params) -> Dict:
    """Matmul weights cast to the compute dtype; norms stay in param
    dtype (_rms_norm does its own f32 math).

    LoRA merge site (models/lora.py): when `{k}_lora_a/b` leaves are
    present the effective weight W + (alpha/r) A@B is formed here, in
    compute dtype, per scanned layer. Every consumer — training layer,
    pipeline stage, KV-cache decoder — flows through this function, so
    adapters apply uniformly. The merge matmul is r*in*out FLOPs,
    ~r/(B*S) of the projection itself."""
    out = {}
    for k, v in layer_params.items():
        if k.endswith("_norm") or "_lora_" in k:
            continue
        if isinstance(v, QuantizedWeight):
            # int8-quantized serving weight: dequant is fused into the
            # matmul (matmul_any), and serving LoRA is the per-slot
            # BGMV delta added AFTER the base projection — merged
            # `_lora_` leaves never coexist with a quantized base
            # (engine install quantizes the bare tree).
            out[k] = v
            continue
        w = v.astype(cfg.dtype)
        a = layer_params.get(k + "_lora_a")
        if a is not None:
            b = layer_params[k + "_lora_b"]
            scale = jnp.asarray(
                cfg.lora_alpha / a.shape[-1], cfg.dtype
            )
            w = w + scale * (a.astype(cfg.dtype) @ b.astype(cfg.dtype))
        out[k] = w
    return out


def _slot_lora_delta(h, a, b, idx, scale):
    """Per-row LoRA delta gathered from a stacked adapter bank — the
    BGMV formulation of multi-adapter serving (serving/adapters.py):
    row i of `h` [B, S, in] uses adapter cache slot idx[i], so the
    delta is scale[idx] * (h @ A[idx]) @ B[idx] with A [S, in, r] and
    B [S, r, out]. Slot 0 holds the all-zero adapter by convention,
    so adapterless rows add an exact zero and the token stream is
    unchanged. rank·in FLOPs per row — noise on the MXU."""
    hr = jnp.einsum("bsi,bir->bsr", h, a[idx].astype(h.dtype))
    d = jnp.einsum("bsr,bro->bso", hr, b[idx].astype(h.dtype))
    return scale[idx].astype(h.dtype)[:, None, None] * d


def _attn_qkv(
    cfg: LlamaConfig, mesh, h, lp, positions, lora=None, tp: int = 1,
    kind: Optional[str] = None, qk_norms=None,
):
    """Projections + RoPE of one block — shared by the training layer
    and the KV-cache decoder (models/decode.py), so there is exactly
    one definition of the attention inputs.

    `lora` (serving only) is a (bank, idx, scale) triple of one
    layer's stacked adapter slices: per-row deltas are added to the
    raw projections BEFORE the head reshape and RoPE — RoPE is linear
    in its input, so a pre-rotation delta equals rotating the
    merged-weight projection.

    `qk_norms` (serving, `cfg.qk_norm`): the layer's (q_norm, k_norm)
    scales; each head of q and k is RMS-normed over its own numbers
    before the rotary turn."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, s, _ = h.shape
    hq = matmul_any(h, lp["wq"], tp=tp)
    hk = matmul_any(h, lp["wk"], tp=tp)
    hv = matmul_any(h, lp["wv"], tp=tp)
    if lora is not None:
        bank, idx, scale = lora
        hq = hq + _slot_lora_delta(
            h, bank["wq_a"], bank["wq_b"], idx, scale
        )
        hk = hk + _slot_lora_delta(
            h, bank["wk_a"], bank["wk_b"], idx, scale
        )
        hv = hv + _slot_lora_delta(
            h, bank["wv_a"], bank["wv_b"], idx, scale
        )
    q = checkpoint_name(hq.reshape(b, s, H, hd), "qkv_proj")
    k = checkpoint_name(hk.reshape(b, s, KV, hd), "qkv_proj")
    v = checkpoint_name(hv.reshape(b, s, KV, hd), "qkv_proj")
    q = constrain(q, mesh, ("data", "fsdp"), "seq", "tensor", None)
    k = constrain(k, mesh, ("data", "fsdp"), "seq", "tensor", None)
    v = constrain(v, mesh, ("data", "fsdp"), "seq", "tensor", None)
    # a kind's own rotary parameters where the config gives a layer
    # pattern; else the one base every layer shares
    rope = cfg.rope_theta if kind is None else cfg.rope_of(kind)
    if isinstance(rope, RopeSpec) and not rope.yarn_factor:
        rope = rope.theta
    if qk_norms is not None:
        q = _rms_norm(q, qk_norms[0], cfg.norm_eps)
        k = _rms_norm(k, qk_norms[1], cfg.norm_eps)
    q = _rope(q, positions, rope)
    k = _rope(k, positions, rope)
    return q, k, v


def _attn_residual(
    cfg: LlamaConfig, mesh, x, attn, lp, lora=None, tp: int = 1
):
    """Output projection + residual (shared with decode). `lora` adds
    the per-slot wo delta to the projection (same triple as
    `_attn_qkv`)."""
    b, s, _ = x.shape
    attn = checkpoint_name(
        attn.reshape(b, s, cfg.n_heads * cfg.head_dim), "attn_out"
    )
    o = checkpoint_name(matmul_any(attn, lp["wo"], tp=tp), "attn_proj")
    if lora is not None:
        bank, idx, scale = lora
        o = o + _slot_lora_delta(
            attn, bank["wo_a"], bank["wo_b"], idx, scale
        )
    return x + constrain(o, mesh, ("data", "fsdp"), "seq", None)


def _mlp_residual(cfg: LlamaConfig, mesh, x, layer_params, lp, tp: int = 1):
    """Dense-SwiGLU / MoE feed-forward + residual (shared with decode).
    Returns (x, moe aux loss — zero for dense)."""
    h = _rms_norm(x, layer_params["mlp_norm"], cfg.norm_eps)
    if cfg.n_experts > 0 and cfg.moe_routing == "dropless":
        raise ValueError(
            "moe_routing='dropless' is served through "
            "models/decode.py (moe.dropless_moe); this layer is the "
            "capacity-bounded training one and would drop tokens"
        )
    if cfg.n_experts > 0:
        from dlrover_tpu.models.moe import moe_mlp

        ff_out, moe_metrics = moe_mlp(
            cfg.moe,
            {k: layer_params[k]
             for k in ("router", "we_gate", "we_up", "we_down")},
            h,
            mesh=mesh,
            compute_dtype=cfg.dtype,
        )
        x = x + constrain(ff_out, mesh, ("data", "fsdp"), "seq", None)
        return x, moe_metrics["moe_aux_loss"]
    x = x + _swiglu(mesh, h, lp["w_gate"], lp["w_up"], lp["w_down"], tp)
    return x, jnp.zeros((), jnp.float32)


def _swiglu(mesh, h, w_gate, w_up, w_down, tp: int = 1):
    """down(silu(gate(h)) * up(h)): the dense feed-forward, and a
    shared expert's (models/decode.py)."""
    gate = jax.nn.silu(
        checkpoint_name(matmul_any(h, w_gate, tp=tp), "mlp_gate")
    )
    up = checkpoint_name(matmul_any(h, w_up, tp=tp), "mlp_up")
    ff = constrain(
        gate * up, mesh, ("data", "fsdp"), "seq", "tensor"
    )
    return constrain(
        checkpoint_name(matmul_any(ff, w_down, tp=tp), "mlp_down"),
        mesh, ("data", "fsdp"), "seq", None,
    )


def _layer(cfg: LlamaConfig, mesh, x, layer_params, positions):
    """One decoder block on [B, S, D] activations."""
    lp = _compute_weights(cfg, layer_params)
    with jax.named_scope("attn"):
        x = _attn_block(cfg, mesh, x, layer_params, lp, positions)
    with jax.named_scope("mlp"):
        return _mlp_residual(cfg, mesh, x, layer_params, lp)


def _attn_block(cfg: LlamaConfig, mesh, x, layer_params, lp, positions):
    """The attention half of `_layer`: norm, projections, attention,
    output projection and residual."""
    h = _rms_norm(x, layer_params["attn_norm"], cfg.norm_eps)
    q, k, v = _attn_qkv(cfg, mesh, h, lp, positions)
    sp_live = (
        mesh is not None
        and cfg.seq_parallel != "none"
        and dict(zip(mesh.axis_names, mesh.devices.shape)).get("seq", 1)
        > 1
    )
    if sp_live:
        from dlrover_tpu.parallel.sequence import sp_attention

        attn = sp_attention(
            q, k, v, mesh, mode=cfg.seq_parallel, causal=True
        )
    else:
        # a fused kernel cannot be partitioned by the compiler: hand
        # the dispatcher the mesh so it shard_maps the kernel over the
        # batch and tensor axes. Not under a live pipe axis — there
        # this layer already runs inside the pipeline's own shard_map.
        axes = (
            dict(zip(mesh.axis_names, mesh.devices.shape))
            if mesh is not None else {}
        )
        flat = axes.get("pipe", 1) == 1 and axes.get("seq", 1) == 1
        attn = dot_product_attention(
            q, k, v, causal=True, impl=cfg.attn_impl,
            block_q=cfg.attn_block_q or None,
            block_k=cfg.attn_block_k or None,
            tp=axes.get("tensor", 1) if flat else 1,
            mesh=mesh if flat else None,
        )
    return _attn_residual(cfg, mesh, x, attn, lp)


def refuse_training(cfg: LlamaConfig) -> None:
    """`apply` is the training forward: every layer full, one rotary
    base, capacity routing. A configuration that needs more is
    refused by name, never run as something else."""
    asked = [
        name for name, on in (
            ("layer_pattern with window layers", cfg.hybrid),
            ("moe_routing='dropless'",
             cfg.n_experts > 0 and cfg.moe_routing == "dropless"),
            ("rope_full/rope_window",
             cfg.rope_full is not None or cfg.rope_window is not None),
            ("latent attention (kv_lora_rank)", cfg.latent),
            ("first_k_dense leading dense layers", cfg.first_k_dense > 0),
            ("n_shared_experts", cfg.n_shared_experts > 0),
            ("moe_scoring='sigmoid'", cfg.moe_scoring != "softmax"),
            ("experts_held", bool(cfg.experts_held)),
            ("normalised q and k (qk_norm)", cfg.qk_norm),
            ("generation by diffusion over blocks (block_length: its "
             "loss is a block-diffusion loss over a doubled sequence)",
             cfg.block_length > 0),
        ) if on
    ]
    if asked:
        raise ValueError(
            "llama.apply (training) does not run " + ", ".join(asked)
            + ": this configuration is served through "
            "models/decode.py only"
        )


def apply(
    cfg: LlamaConfig,
    params: Params,
    tokens: jax.Array,
    mesh=None,
    positions: Optional[jax.Array] = None,
    return_aux: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """Forward pass: tokens [B, S] int32 → logits [B, S, vocab] f32.
    With return_aux, also returns the summed per-layer MoE aux loss.
    With return_hidden, returns post-final-norm hidden states [B,S,D]
    instead of logits (fused-CE path)."""
    refuse_training(cfg)
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    with jax.named_scope("embed"):
        x = params["embed"]["weight"].astype(cfg.dtype)[tokens]
        x = constrain(x, mesh, ("data", "fsdp"), "seq", None)

    from dlrover_tpu.parallel.pipeline import num_stages, pipeline_apply

    n_stages = num_stages(mesh) if mesh is not None else 1
    if n_stages > 1:
        # GPipe over the pipe axis; positions ride in the state tree so
        # they split into microbatches alongside the activations
        if cfg.n_layers % n_stages:
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible by pipe degree "
                f"{n_stages}"
            )
        n_mb = cfg.pipeline_microbatches or n_stages

        def layer_fn(lp, st, _unused=None):
            y, aux = _layer(cfg, mesh, st["h"], lp, st["pos"])
            return {"h": y, "pos": st["pos"], "aux": st["aux"] + aux}

        state = pipeline_apply(
            layer_fn,
            mesh,
            params["layers"],
            {
                "h": x,
                "pos": positions,
                "aux": jnp.zeros((b,), jnp.float32),
            },
            n_microbatches=n_mb,
        )
        x = state["h"]
        aux_per_layer = jnp.mean(state["aux"])[None]
    else:
        def body(carry, layer_params):
            y, aux = _layer(cfg, mesh, carry, layer_params, positions)
            return y, aux

        if cfg.remat:
            from dlrover_tpu.parallel import remat

            body = remat.apply_remat(
                body, remat.scan_policy(cfg.remat_policy)
            )
        with jax.named_scope("layers"):
            x, aux_per_layer = jax.lax.scan(body, x, params["layers"])

    with jax.named_scope("lm_head_loss"):
        x = _rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if return_hidden:
        # pre-head hidden states for the fused-CE loss path (the
        # [B,S,V] logits are never formed there)
        if return_aux:
            return x, jnp.sum(aux_per_layer)
        return x
    with jax.named_scope("lm_head_loss"):
        head = _head_matrix(cfg, params)
        logits = (x @ head).astype(jnp.float32)
        logits = constrain(
            logits, mesh, ("data", "fsdp"), "seq", "tensor"
        )
    if return_aux:
        return logits, jnp.sum(aux_per_layer)
    return logits


def _head_matrix(cfg: LlamaConfig, params: Params):
    """The unembedding operand for `matmul_any(x, head)`. Tied
    embeddings are NEVER quantized (the token gather at embedding
    time needs the dense table anyway, so there are no bytes to
    save); an untied lm_head may arrive int8-quantized from the
    serving install and is returned as-is — its dequant fuses into
    the logits matmul."""
    if cfg.tie_embeddings:
        return params["embed"]["weight"].astype(cfg.dtype).T
    w = params["lm_head"]["weight"]
    if isinstance(w, QuantizedWeight):
        return w
    return w.astype(cfg.dtype)


def loss_fn(
    cfg: LlamaConfig,
    params: Params,
    batch: Dict[str, jax.Array],
    mesh=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy. batch: tokens [B,S], optional loss_mask."""
    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    mask = batch.get("loss_mask")
    use_fused = cfg.fused_ce and cfg.seq_parallel == "none"
    if use_fused:
        from dlrover_tpu.ops.fused_ce import fused_cross_entropy

        hidden, aux = apply(
            cfg, params, tokens[:, :-1], mesh=mesh,
            return_aux=True, return_hidden=True,
        )
        with jax.named_scope("lm_head_loss"):
            head = _head_matrix(cfg, params)
            m = mask[:, 1:] if mask is not None else None
            loss_sum, weight = fused_cross_entropy(
                hidden, head, targets, m
            )
            weight = jnp.maximum(weight, 1.0)
            loss = loss_sum / weight
    else:
        logits, aux = apply(
            cfg, params, tokens[:, :-1], mesh=mesh, return_aux=True
        )
        with jax.named_scope("lm_head_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, targets[..., None], axis=-1
            ).squeeze(-1)
            if mask is not None:
                m = mask[:, 1:].astype(nll.dtype)
                total = jnp.maximum(m.sum(), 1.0)
                loss = (nll * m).sum() / total
                weight = total
            else:
                loss = nll.mean()
                weight = jnp.asarray(nll.size, jnp.float32)
    metrics = {"loss": loss, "loss_weight": weight}
    if cfg.n_experts > 0:
        loss = loss + aux
        metrics["moe_aux_loss"] = aux
    # loss_weight lets grad-accum weight microbatches by token count
    return loss, metrics


def num_params(cfg: LlamaConfig) -> int:
    L, D, M, V = cfg.n_layers, cfg.dim, cfg.mlp_dim, cfg.vocab_size
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.n_experts > 0:
        mlp = cfg.n_experts * 3 * D * M + D * cfg.n_experts
    else:
        mlp = 3 * D * M
    per_layer = (
        D * H * hd + 2 * D * KV * hd + H * hd * D + mlp + 2 * D
    )
    total = V * D + L * per_layer + D
    if not cfg.tie_embeddings:
        total += D * V
    return total


def flops_per_token(
    cfg: LlamaConfig, seq_len: int, causal: bool = False
) -> float:
    """Approx training FLOPs/token: 6*N + attention term (for MFU).

    causal=False is the PaLM convention (full S x S score matrix
    credited); causal=True credits only the lower-triangular blocks the
    causal kernel actually computes (~(S+1)/2S of full — the
    conservative accounting, used for the bench headline)."""
    n = num_params(cfg)
    attn = 12.0 * cfg.n_layers * cfg.dim * seq_len
    if causal:
        attn *= (seq_len + 1) / (2.0 * seq_len)
    return 6.0 * n + attn
