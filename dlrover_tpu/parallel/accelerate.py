"""auto-acceleration: (model fns, strategy) → sharded init + train step.

Reference parity: atorch.auto_accelerate (atorch/atorch/auto/accelerate.py:406)
decouples model definition from the parallel strategy by rewriting torch
modules per a 16-method optimization library. The TPU equivalent is far
smaller because XLA does the rewriting: a Strategy is a mesh spec plus
partition rules plus jit knobs (remat/donation/grad-accum); `accelerate`
jits one SPMD program over the mesh and GSPMD inserts the collectives.
"""

from dataclasses import dataclass, field
from functools import wraps
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.parallel import amp, remat
from dlrover_tpu.parallel.mesh import BATCH_AXES, MeshSpec
from dlrover_tpu.parallel.sharding import (
    Rules,
    _filter_spec,
    constrain,
    tree_shardings,
)
from dlrover_tpu.utils.program_stats import (
    abstractify,
    device_memory_bytes,
    extract_program_stats,
)

TrainState = Dict[str, Any]
LossFn = Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]


@dataclass(frozen=True)
class Strategy:
    """Declarative acceleration strategy (the auto_accelerate analogue).

    grad_accum > 1 keeps the *global* batch fixed as the job scales
    (reference: ElasticTrainer trainer/torch/elastic/trainer.py) — the
    train step scans over a leading microbatch axis.

    precision/remat/loss_scale are the AMP + activation-checkpoint
    optimizations of the reference's library (amp_optimization.py,
    checkpoint_optimization.py) expressed as jit knobs: params are cast
    to the policy's compute dtype before the loss, the loss body is
    wrapped in jax.checkpoint with the named policy, and loss scaling
    (for f16 experiments; bf16 needs none) skips non-finite steps.
    """

    mesh: MeshSpec = field(default_factory=MeshSpec)
    grad_accum: int = 1
    donate_state: bool = True
    batch_spec: Tuple = (BATCH_AXES, None)  # [batch, seq]
    precision: str = "f32"       # "f32" | "bf16" | "half" (amp.get_policy)
    remat: str = "none"          # remat.resolve_policy names
    remat_save_names: Tuple = ()
    loss_scale: bool = False


@dataclass
class Accelerated:
    """What accelerate() hands back to the trainer."""

    mesh: Mesh
    strategy: Strategy
    init: Callable[[jax.Array], TrainState]
    train_step: Callable[[TrainState, Any], Tuple[TrainState, Dict]]
    eval_step: Optional[Callable] = None
    state_shardings: Any = None

    def batch_sharding(
        self, x, with_accum: bool = True
    ) -> NamedSharding:
        """The NamedSharding one batch leaf gets on this mesh."""
        spec = P(*self.strategy.batch_spec)
        if self.strategy.grad_accum > 1 and with_accum:
            spec = P(None, *self.strategy.batch_spec)
        nd = getattr(x, "ndim", 0)
        entries = list(spec)[:nd]
        filtered = _filter_spec(
            P(*entries), self.mesh, getattr(x, "shape", ())
        )
        return NamedSharding(self.mesh, filtered)

    def shard_batch(self, batch, with_accum: bool = True) -> Any:
        """Place a host batch on the mesh. `with_accum=False` for
        unfolded batches (eval) when the train strategy accumulates."""
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(
                x, self.batch_sharding(x, with_accum)
            ),
            batch,
        )

    def abstract_batch(self, batch, with_accum: bool = True) -> Any:
        """Avals of shard_batch's result with NO device transfer —
        for AOT lowering (profile_program)."""
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape,
                x.dtype,
                sharding=self.batch_sharding(x, with_accum),
            )
            if hasattr(x, "shape")
            else x,
            batch,
        )

    def profile_program(self, state, batch):
        """Cost/memory stats of the compiled train step (reference TF
        graph profile extractor → brain; utils/program_stats.py). Uses
        AOT lower+compile on abstract avals — hits the compilation
        cache when the step already ran, so this is cheap after the
        first step. `batch` may be real arrays or avals (abstract_batch)."""
        lowered = self.train_step.lower(*abstractify((state, batch)))
        return extract_program_stats(lowered.compile())


def _at_rung(rung: str, fn: Callable) -> Callable:
    """`fn` traced with the model's "auto" layer scans at `rung`: a
    function of its own, so a trace of its own in the caches of
    `jax.jit` and `jax.checkpoint`."""

    @wraps(fn)
    def at_rung(*args):
        with remat.tracing_at(rung):
            return fn(*args)

    return at_rung


def accelerate(
    init_params: Callable[[jax.Array], Any],
    loss_fn: LossFn,
    rules: Rules,
    optimizer: optax.GradientTransformation,
    strategy: Optional[Strategy] = None,
    devices=None,
) -> Accelerated:
    """Build the sharded training program.

    init_params(key) -> params pytree
    loss_fn(params, batch, mesh) -> (loss, metrics)
    rules: partition rules for the param pytree

    Where this process's devices of the mesh state a memory limit,
    `train_step` is a `remat.LadderStep`: a model whose layer scans
    run `remat_policy="auto"` gets the rung the compiled step has
    room for, chosen when the step first meets real shapes, and again
    by the next `accelerate()` of a rebuilt job. It is called and
    lowered like the jitted function it is elsewhere, and is the kept
    rung's jitted function once that has run.
    """
    strategy = strategy or Strategy()
    mesh = strategy.mesh.build(devices)
    policy = amp.get_policy(strategy.precision)

    def _loss_at(rung: Optional[str] = None):
        """The loss, a model's "auto" layer scans at `rung` (None: no
        one chooses, "full"). A function of its own a rung, under a
        `jax.checkpoint` of its own: that too keeps its traces by
        function and avals, and one wrap for all would trace the top
        rung once and hand it to every rung below."""

        def _loss_body(params, batch):
            return loss_fn(policy.cast_to_compute(params), batch, mesh)

        if rung is not None:
            _loss_body = _at_rung(rung, _loss_body)
        if strategy.remat != "none":
            _loss_body = remat.apply_remat(
                _loss_body, strategy.remat, strategy.remat_save_names
            )
        return _loss_body

    def _constrain_tree(tree):
        """Apply partition rules anywhere in the state tree: optimizer
        moments live at paths like 'opt_state/0/mu/layers/wq', and the
        rules use re.search, so param rules bind them too."""
        shardings = tree_shardings(tree, mesh, rules)
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, shardings
        )

    def _init(key):
        params = init_params(key)
        opt_state = optimizer.init(params)
        state = {
            "params": params,
            "opt_state": opt_state,
            "step": jnp.zeros((), jnp.int32),
        }
        if strategy.loss_scale:
            state["loss_scale"] = amp.init_loss_scale()
        return _constrain_tree(state)

    init_jit = jax.jit(_init)

    def _grads(loss_body, params, batch, scale=None):
        def f(p, b):
            loss, m = loss_body(p, b)
            if scale is not None:
                loss = loss * scale.astype(loss.dtype)
            return loss, m

        (loss, metrics), grads = jax.value_and_grad(f, has_aux=True)(
            params, batch
        )
        if scale is not None:
            loss = loss / scale.astype(loss.dtype)
        return loss, metrics, grads

    def _step(loss_body, state, batch):
        params = state["params"]
        ls = state.get("loss_scale") if strategy.loss_scale else None
        scale = ls.scale if ls is not None else None
        if strategy.grad_accum > 1:
            # Microbatches are weighted by their valid-token count
            # (metrics["loss_weight"] if the loss_fn provides one, else
            # uniform) so a masked loss matches the single big-batch
            # step instead of over-weighting sparse microbatches.
            def micro(carry, mb):
                acc_grads, acc_loss, acc_w = carry
                loss, m, grads = _grads(loss_body, params, mb, scale)
                w = m.get("loss_weight", jnp.ones((), jnp.float32))
                w = w.astype(jnp.float32)
                acc_grads = jax.tree_util.tree_map(
                    lambda a, g: a + g * w, acc_grads, grads
                )
                return (acc_grads, acc_loss + loss * w, acc_w + w), None

            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss_sum, w_sum), _ = jax.lax.scan(
                micro,
                (zero, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                batch,
            )
            inv = 1.0 / jnp.maximum(w_sum, 1e-8)
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            loss = loss_sum * inv
            metrics = {"loss": loss}
        else:
            loss, metrics, grads = _grads(loss_body, params, batch, scale)

        if ls is not None:
            grads = amp.unscale_grads(grads, ls)

        with jax.named_scope("optimizer_update"):
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], params
            )
            new_params = optax.apply_updates(params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        new_state = {
            "params": new_params,
            "opt_state": new_opt,
            "step": state["step"] + 1,
        }
        if ls is not None:
            # skip the step entirely when grads overflowed, then back off
            finite = amp.all_finite(grads)
            keep = lambda n, o: jnp.where(finite, n, o)
            new_state["params"] = jax.tree_util.tree_map(
                keep, new_state["params"], params
            )
            new_state["opt_state"] = jax.tree_util.tree_map(
                keep, new_state["opt_state"], state["opt_state"]
            )
            new_state["loss_scale"] = amp.adjust_loss_scale(ls, finite)
            metrics["loss_scale"] = new_state["loss_scale"].scale
        new_state = _constrain_tree(new_state)
        return new_state, metrics

    def _jit_step(rung: Optional[str] = None):
        loss_body = _loss_at(rung)

        def _train_step(state, batch):
            return _step(loss_body, state, batch)

        return jax.jit(
            _train_step,
            donate_argnums=(0,) if strategy.donate_state else (),
        )

    # the limit of a device this process owns: the mesh's first may
    # be another process's, which states nothing here, and every
    # process of a job has to decide alike
    limit = device_memory_bytes(mesh.local_devices[0])
    if limit > 0:
        train_jit = remat.LadderStep(_jit_step, limit)
    else:
        # no limit stated (the CPU): a model's "auto" is "full"
        train_jit = _jit_step()

    eval_loss = _loss_at()

    def _eval_step(state, batch):
        loss, metrics = eval_loss(state["params"], batch)
        return metrics

    # the NamedSharding tree of the train state, derived without
    # materializing any arrays — consumers: checkpoint restore onto a
    # fresh mesh (engine.load target) and auto_engine memory analysis
    abstract_state = jax.eval_shape(_init, jax.random.PRNGKey(0))
    state_shardings = tree_shardings(abstract_state, mesh, rules)

    return Accelerated(
        mesh=mesh,
        strategy=strategy,
        init=init_jit,
        train_step=train_jit,
        eval_step=jax.jit(_eval_step),
        state_shardings=state_shardings,
    )
