"""Device mesh construction — the TPU replacement for process groups.

Reference parity: atorch/atorch/distributed/distributed.py:323
`create_parallel_group([("tensor",4),("pipeline",2),("data",2)])` builds
nested NCCL groups. Here the same parallel-mode product is ONE
`jax.sharding.Mesh`; named mesh axes replace named process groups and XLA
emits the collectives over ICI/DCN.

Canonical axis order (outermost → innermost over the device list):
``("pipe", "data", "fsdp", "expert", "seq", "tensor")`` — tensor parallelism
innermost so its collectives ride nearest-neighbor ICI links.
"""

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_ORDER: Tuple[str, ...] = (
    "pipe",
    "data",
    "fsdp",
    "expert",
    "seq",
    "tensor",
)

# Axes over which the global batch is split.
BATCH_AXES: Tuple[str, ...] = ("data", "fsdp")


@dataclass(frozen=True)
class MeshSpec:
    """Declarative parallelism layout. Sizes multiply to the device count;
    any axis may be 1 (present but inert — keeps PartitionSpecs uniform)."""

    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1

    @property
    def axis_sizes(self) -> Dict[str, int]:
        return {
            "pipe": self.pipe,
            "data": self.data,
            "fsdp": self.fsdp,
            "expert": self.expert,
            "seq": self.seq,
            "tensor": self.tensor,
        }

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.axis_sizes.values():
            n *= s
        return n

    @property
    def batch_shards(self) -> int:
        return self.data * self.fsdp

    def with_updates(self, **kw) -> "MeshSpec":
        cur = {
            "data": self.data,
            "fsdp": self.fsdp,
            "tensor": self.tensor,
            "seq": self.seq,
            "expert": self.expert,
            "pipe": self.pipe,
        }
        cur.update(kw)
        return MeshSpec(**cur)

    def build(self, devices: Optional[Sequence] = None) -> Mesh:
        if devices is None:
            devices = jax.devices()
        n = self.num_devices
        if n > len(devices):
            raise ValueError(
                f"MeshSpec needs {n} devices, only {len(devices)} available"
            )
        devices = list(devices)[:n]
        num_slices = len({_slice_id(d) for d in devices})
        if num_slices > 1:
            return self._build_hybrid(devices, num_slices)
        shape = tuple(self.axis_sizes[a] for a in AXIS_ORDER)
        try:
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=devices
            )
        except (ValueError, AssertionError):
            # CPU/virtual devices: topology-aware layout unavailable.
            dev_array = np.asarray(devices).reshape(shape)
        return Mesh(dev_array, AXIS_ORDER)

    def _dcn_factors(self, num_slices: int) -> Dict[str, int]:
        """Split `num_slices` across the batch axes (data first, then
        fsdp): gradient all-reduce / reduce-scatter tolerate DCN
        latency, while tensor/seq/pipe collectives are per-layer and
        must stay on ICI (SURVEY §2.7; reference
        atorch/distributed/distributed.py:505-520 picks groups by
        fabric hierarchy the same way)."""
        import math

        dcn = {a: 1 for a in AXIS_ORDER}
        rem = num_slices
        for axis in ("data", "fsdp"):
            g = math.gcd(self.axis_sizes[axis], rem)
            dcn[axis] = g
            rem //= g
        if rem != 1:
            raise ValueError(
                f"{num_slices} slices cannot be absorbed by the batch "
                f"axes (data={self.data}, fsdp={self.fsdp}): model "
                f"axes must not span DCN — resize data/fsdp so their "
                f"product is divisible by the slice count"
            )
        return dcn

    def _build_hybrid(self, devices: Sequence, num_slices: int) -> Mesh:
        """Multi-slice topology: per-slice (ICI) mesh per slice, outer
        (DCN) product across slices — jax's hybrid mesh when the
        topology is real, manual assembly for virtual/CPU devices."""
        dcn = self._dcn_factors(num_slices)
        ici_shape = tuple(
            self.axis_sizes[a] // dcn[a] for a in AXIS_ORDER
        )
        dcn_shape = tuple(dcn[a] for a in AXIS_ORDER)
        try:
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape,
                dcn_shape,
                devices=devices,
                allow_split_physical_axes=True,
            )
        except (ValueError, AssertionError, KeyError, AttributeError):
            # virtual devices: group by slice, lay each slice out as
            # the ICI block, then interleave so the DCN factor is the
            # OUTER (slow) component of every merged axis
            groups: Dict[int, list] = {}
            for d in devices:
                groups.setdefault(_slice_id(d), []).append(d)
            per_slice_n = 1
            for s in ici_shape:
                per_slice_n *= s
            if any(len(g) != per_slice_n for g in groups.values()):
                # truncation cut mid-slice (or slices are ragged): a
                # hybrid layout is impossible — fall back to a flat
                # mesh rather than crash (DCN-suboptimal but valid)
                import logging

                logging.getLogger(__name__).warning(
                    "uneven slice groups %s for ici shape %s — "
                    "building a flat (non-hybrid) mesh",
                    {k: len(g) for k, g in groups.items()},
                    ici_shape,
                )
                return Mesh(
                    np.asarray(devices).reshape(
                        tuple(
                            self.axis_sizes[a] for a in AXIS_ORDER
                        )
                    ),
                    AXIS_ORDER,
                )
            per_slice = np.stack(
                [
                    np.asarray(groups[k], dtype=object).reshape(
                        ici_shape
                    )
                    for k in sorted(groups)
                ]
            )  # (num_slices, *ici_shape)
            k = len(AXIS_ORDER)
            arr = per_slice.reshape(dcn_shape + ici_shape)
            perm = [x for i in range(k) for x in (i, i + k)]
            dev_array = arr.transpose(perm).reshape(
                tuple(self.axis_sizes[a] for a in AXIS_ORDER)
            )
        return Mesh(dev_array, AXIS_ORDER)

    @classmethod
    def fit(
        cls,
        n_devices: int,
        tensor: int = 1,
        seq: int = 1,
        expert: int = 1,
        pipe: int = 1,
        data: int = 1,
    ) -> "MeshSpec":
        """Fill the fsdp axis with whatever devices remain — the default
        strategy (reference default: FSDP/zero over all ranks)."""
        used = tensor * seq * expert * pipe * data
        if n_devices % used:
            raise ValueError(
                f"{n_devices} devices not divisible by {used} "
                f"(tensor*seq*expert*pipe*data)"
            )
        return cls(
            data=data,
            fsdp=n_devices // used,
            tensor=tensor,
            seq=seq,
            expert=expert,
            pipe=pipe,
        )


def _slice_id(device) -> int:
    """Which slice (DCN island) a device belongs to. Real multi-slice
    TPU devices carry `slice_index`; everything else is one slice."""
    idx = getattr(device, "slice_index", None)
    if idx is not None:
        return int(idx)
    return 0


def batch_spec(extra: Tuple = ()) -> PartitionSpec:
    """PartitionSpec for [batch, ...] arrays: batch split over data+fsdp."""
    return PartitionSpec(BATCH_AXES, *extra)


def named(mesh: Mesh, spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, spec)


def local_mesh_spec(n_devices: Optional[int] = None) -> MeshSpec:
    """Pure data-parallel mesh over local devices (the dev default)."""
    if n_devices is None:
        n_devices = jax.local_device_count()
    return MeshSpec.fit(n_devices)


# --------------------------------------------------------------------------
# Serving replica meshes: a replica is a 1-D tensor slice of the LOCAL
# devices (heartbeats/auto-scaling count chips = replicas × slice size).
# Serving code must build meshes through these helpers — never a raw
# jax.sharding.Mesh — so the mesh layer stays single-sourced here
# (enforced by tests/test_layering.py).

SERVING_TP_AXIS = "tp"


def serving_mesh_spec(
    tp: int = 1,
    n_kv_heads: Optional[int] = None,
    n_devices: Optional[int] = None,
) -> MeshSpec:
    """Validated `local_mesh_spec` sibling for a serving replica: a pure
    tensor slice (``MeshSpec(tensor=tp)``) of the local devices. Raises
    ``ValueError`` when the host has fewer devices than the slice or when
    `n_kv_heads` (if given) does not divide evenly over `tp` — the KV
    banks shard the head axis, so a non-divisible head count cannot be
    laid out."""
    if n_devices is None:
        n_devices = jax.local_device_count()
    if tp < 1:
        raise ValueError(f"serving mesh tp must be >= 1, got {tp}")
    if tp > n_devices:
        raise ValueError(
            f"serving mesh needs tp={tp} local devices, host has only "
            f"{n_devices} — shrink mesh_spec or run on a larger slice"
        )
    if n_kv_heads is not None and n_kv_heads % tp != 0:
        raise ValueError(
            f"n_kv_heads={n_kv_heads} is not divisible by tp={tp}: the "
            f"KV cache shards the head axis, so tp must divide the KV "
            f"head count — use tp in "
            f"{[t for t in range(1, n_kv_heads + 1) if n_kv_heads % t == 0]}"
        )
    return MeshSpec(tensor=tp)


def serving_mesh(
    tp: int = 1,
    devices: Optional[Sequence] = None,
    n_kv_heads: Optional[int] = None,
) -> Mesh:
    """1-D ``("tp",)`` mesh over the first `tp` local devices. Built via
    ``MeshSpec.build`` (topology-aware layout on real TPUs, reshape
    fallback on virtual/CPU devices) then flattened to the single
    serving axis, so serving and training share one mesh layer."""
    if devices is None:
        devices = jax.local_devices()
    spec = serving_mesh_spec(
        tp, n_kv_heads=n_kv_heads, n_devices=len(devices)
    )
    full = spec.build(devices)
    return Mesh(
        full.devices.reshape((tp,)), (SERVING_TP_AXIS,)
    )


def serving_kv_spec() -> PartitionSpec:
    """Spec for the serving KV banks — dense slot bank
    ``[L, slots, cells, KV, hd]``, paged pool
    ``[L, pages, page_size, KV, hd]`` and prefix pool all keep the KV
    head axis at dim 3; quantization scales share the layout with
    hd==1. Only the head axis is sharded: rows/cells are host-planned
    (slot tables, page tables) and must stay addressable everywhere."""
    return PartitionSpec(None, None, None, SERVING_TP_AXIS)


def serving_mesh_tp(mesh: Optional[Mesh]) -> int:
    """Size of the serving ``"tp"`` axis (1 when no mesh is threaded
    or the mesh has no serving axis) — the ops kernel wrappers and
    models/decode.py key their dispatch on this."""
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        SERVING_TP_AXIS, 1
    )


def serving_head_specs(mesh: Mesh) -> Dict[str, PartitionSpec]:
    """Per-shard PartitionSpecs for shard_mapping the attention
    kernels over the serving ``"tp"`` axis — the ONE layout source
    the ops/ wrappers consume (a second spec table could silently
    drift from the NamedShardings decode.py constrains q/k/v to):

    - ``"qkv"``: prefill/verify activations ``[B, S, H, D]`` — head
      axis (dim 2) split, everything else shard-local.
    - ``"q1"``: the single-token decode query ``[B, H, hd]`` — head
      axis at dim 1.
    - ``"pool"``: a stacked page-pool array ``[L, pages, page_size,
      KV, hd]`` (scales ride with hd==1) — KV head axis at dim 3.
    - ``"replicated"``: host-planned operands (page tables, lengths)
      every shard reads whole.

    Attention is embarrassingly parallel over heads, so bodies using
    these specs need NO collectives; the replicated-output constraint
    before the out-projection stays with the caller (decode.py)."""
    if SERVING_TP_AXIS not in getattr(mesh, "axis_names", ()):
        raise ValueError(
            f"serving_head_specs needs a mesh with a "
            f"{SERVING_TP_AXIS!r} axis (serving_mesh builds one); got "
            f"axes {getattr(mesh, 'axis_names', None)}"
        )
    ax = SERVING_TP_AXIS
    return {
        "qkv": PartitionSpec(None, None, ax, None),
        "q1": PartitionSpec(None, ax, None),
        "pool": PartitionSpec(None, None, None, ax, None),
        "replicated": PartitionSpec(),
    }


def attention_qkv_spec(mesh: Mesh) -> Tuple[PartitionSpec, int]:
    """(spec, head-shard degree) for shard_mapping a fused attention
    kernel over ``[B, S, H, D]`` activations on ANY mesh this module
    builds — the TPU compiler refuses to partition a Pallas kernel
    itself, so under more than one device the kernel must run inside
    a shard_map whose specs match what GSPMD already gave q/k/v:

    - a serving mesh: heads on ``"tp"`` (`serving_head_specs`);
    - a training mesh: batch on the batch axes (data, fsdp), heads on
      ``"tensor"`` — the layout models/llama.py constrains q/k/v to.
      The seq/pipe/expert axes stay out: sequence parallelism has its
      own attention (parallel/sequence.py) and callers only come here
      when those axes have size 1.

    Attention mixes neither batch rows nor heads, so the body needs
    no collectives either way."""
    if SERVING_TP_AXIS in mesh.axis_names:
        return (
            serving_head_specs(mesh)["qkv"], serving_mesh_tp(mesh)
        )
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch = tuple(a for a in BATCH_AXES if sizes.get(a, 1) > 1)
    heads = "tensor" if sizes.get("tensor", 1) > 1 else None
    return (
        PartitionSpec(batch or None, None, heads, None),
        sizes.get("tensor", 1),
    )


def serving_adapter_specs(mesh: Mesh) -> Dict[str, PartitionSpec]:
    """PartitionSpecs for the stacked device adapter banks a serving
    replica gathers per-slot LoRA deltas from (serving/adapters.py):
    per target ``t``, ``t_a`` is ``[L, S, in, r]`` and ``t_b`` is
    ``[L, S, r, out]`` (S = device cache slots, slot 0 the zero
    adapter), plus a ``scale`` vector ``[S]``.

    Layout mirrors the base projections' serving placement so the
    delta adds zero collectives under tp>1: wq/wk/wv are
    output-column split on ``"tp"``, so their B banks shard the
    output axis the same way while the tiny ``x @ A`` rank
    activations stay replicated (rank never shards); wo is replicated
    like the base out-projection, so its whole bank is too."""
    if SERVING_TP_AXIS not in getattr(mesh, "axis_names", ()):
        raise ValueError(
            f"serving_adapter_specs needs a mesh with a "
            f"{SERVING_TP_AXIS!r} axis (serving_mesh builds one); got "
            f"axes {getattr(mesh, 'axis_names', None)}"
        )
    col = PartitionSpec(None, None, None, SERVING_TP_AXIS)
    rep = PartitionSpec()
    return {
        "wq_b": col, "wk_b": col, "wv_b": col,
        "wq_a": rep, "wk_a": rep, "wv_a": rep,
        "wo_a": rep, "wo_b": rep,
        "scale": rep,
    }


def serving_weight_quant_specs() -> Tuple[Tuple[str, PartitionSpec], ...]:
    """(path-regex, PartitionSpec) placement rules for the int8
    weight-quantized serving tree (engine weight_quant="int8").

    Quantized weights are stored OUTPUT-MAJOR ([L, O, K] int8 values,
    [L, O, K/block] f32 scales — ops/quantization.QuantizedWeight), so
    the column split the dense serving rules put on wq/wk/wv's output
    axis (their LAST dim) lands on axis 1 here, and the scales ride
    the SAME "tp" axis as their int8 blocks: a shard boundary can
    never straddle a quant block, which is what lets an elastic
    resize reshard q8+s8 at any tp without requantizing. Everything
    the dense rules replicate (wo, MLP, unembed) stays replicated by
    the default rule, so these three families are the whole table.
    The dense rules are ``$``-anchored (``layers/wq$``) and cannot
    match the ``.../q8`` children — the weight_quant="none" tree is
    untouched by construction."""
    col = PartitionSpec(None, SERVING_TP_AXIS, None)
    return (
        (r"layers/wq/(q8|s8)$", col),
        (r"layers/wk/(q8|s8)$", col),
        (r"layers/wv/(q8|s8)$", col),
    )


def largest_serving_tp(
    n_chips: int,
    n_kv_heads: Optional[int] = None,
    n_devices: Optional[int] = None,
) -> int:
    """Largest tp degree a shrunk/grown replica can re-form at: the
    biggest t <= n_chips that divides `n_kv_heads` (the KV banks shard
    the head axis) and fits the host's local devices. This is the one
    shrink/grow policy source for serving/elastic.py — a resize that
    picked its tp anywhere else could mint a slice serving_mesh_spec
    would reject. Always >= 1 (tp=1 is every config's fallback)."""
    if n_devices is None:
        n_devices = jax.local_device_count()
    cap = max(1, min(int(n_chips), int(n_devices)))
    for t in range(cap, 0, -1):
        if n_kv_heads is None or n_kv_heads % t == 0:
            return t
    return 1
