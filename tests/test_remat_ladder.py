"""The layer scan keeps what the device has room for: `accelerate()`
takes the rung of a model's `remat_policy="auto"` from the compiled
step's `memory_analysis()` against the device's `bytes_limit`
(`parallel/remat.py` `LadderStep`). The CPU states no limit, so every
case here states one for it (`accelerate.device_memory_bytes`
patched) and reads the rungs' peaks from the CPU compiler: the sizes
are the tiny model's, the control flow is the chip's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from dlrover_tpu.common import trace
from dlrover_tpu.models import llama
from dlrover_tpu.parallel import accelerate as accelerate_mod
from dlrover_tpu.parallel import remat
from dlrover_tpu.parallel.accelerate import Strategy, accelerate
from dlrover_tpu.parallel.mesh import MeshSpec
from dlrover_tpu.utils.program_stats import (
    device_memory_bytes,
    extract_program_stats,
)

AUTO = llama.LlamaConfig.tiny(remat=True, dtype=jnp.float32)
RUNGS = remat.LADDER


def _explicit(rung):
    """The config a user writes to get `rung` and nothing else."""
    if rung == "none":
        return dataclasses.replace(AUTO, remat=False)
    return dataclasses.replace(AUTO, remat_policy=rung)


def _build(cfg, strategy=None, devices=None):
    return accelerate(
        init_params=lambda k: llama.init_params(cfg, k),
        loss_fn=lambda p, b, m: llama.loss_fn(cfg, p, b, mesh=m),
        rules=llama.partition_rules(cfg),
        optimizer=optax.adamw(1e-3),
        strategy=strategy or Strategy(mesh=MeshSpec.fit(1)),
        devices=devices or jax.devices()[:1],
    )


def _batch(acc, rows=4, seq=64):
    tokens = (
        np.arange(rows * (seq + 1), dtype=np.int32).reshape(rows, seq + 1)
        * 7 % AUTO.vocab_size
    )
    return acc.shard_batch({"tokens": tokens})


def _state(acc):
    return acc.init(jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def empty_ring():
    trace.clear()
    yield
    trace.clear()


@pytest.fixture
def limit(monkeypatch):
    """State a `bytes_limit` for the devices `accelerate()` builds on:
    `limit(budget)` leaves `budget` bytes after the margin."""

    def state(budget):
        monkeypatch.setattr(
            accelerate_mod, "device_memory_bytes",
            lambda device=None: float(budget + remat.MARGIN_BYTES),
        )

    return state


@pytest.fixture(scope="module")
def explicit():
    """{rung: (peak bytes, lowered text, loss, grad norm, the
    gradient as Adam's first moment holds it after one step)} of the
    step a user gets by NAMING the rung."""
    out = {}
    for rung in RUNGS:
        acc = _build(_explicit(rung))
        assert not isinstance(acc.train_step, remat.LadderStep)
        state, batch = _state(acc), _batch(acc)
        lowered = acc.train_step.lower(state, batch)
        peak = extract_program_stats(lowered.compile()).peak_hbm_bytes
        state, metrics = acc.train_step(state, batch)
        out[rung] = (
            peak, lowered.as_text(), float(metrics["loss"]),
            float(metrics["grad_norm"]),
            jax.device_get(state["opt_state"][0].mu),
        )
    return out


def _ladders():
    return [
        r[trace.COUNTS] for r in trace.snapshot()
        if r[trace.NAME] == "remat.ladder"
    ]


def _step_compilations():
    return sum(
        r[trace.NAME] == "compile"
        and r[trace.COUNTS]["leg"] == "backend"
        and r[trace.COUNTS]["program"] == "jit(_train_step)"
        for r in trace.snapshot()
    )


def test_the_cpu_states_no_limit_and_auto_is_full_there(explicit):
    assert AUTO.remat_policy == "auto"
    assert llama.LlamaConfig().remat_policy == "auto"
    assert device_memory_bytes() == 0.0
    acc = _build(AUTO)
    assert not isinstance(acc.train_step, remat.LadderStep)
    text = acc.train_step.lower(_state(acc), _batch(acc)).as_text()
    assert text == explicit["full"][1]
    assert not _ladders()


def test_the_rungs_peaks_fall_down_the_ladder(explicit):
    peaks = [explicit[rung][0] for rung in RUNGS]
    assert peaks == sorted(peaks, reverse=True)
    assert len(set(peaks)) == len(RUNGS) == 3


@pytest.mark.parametrize("fits", RUNGS)
def test_the_first_rung_that_fits_is_kept(explicit, limit, fits):
    """A budget of exactly `fits`'s peak: the rungs above it compile
    to more and are let go, it is kept, and the executable compiled
    for the check is the one that runs."""
    trace.watch_compiles()
    limit(explicit[fits][0])
    acc = _build(AUTO)
    assert isinstance(acc.train_step, remat.LadderStep)
    state, batch = _state(acc), _batch(acc)
    before = _step_compilations()
    state, metrics = acc.train_step(state, batch)
    assert acc.train_step.rung == fits
    (said,) = _ladders()
    tried = RUNGS[: RUNGS.index(fits) + 1]
    assert said["rung"] == fits and said["compiled"] == len(tried)
    for rung in tried:
        assert said[f"peak_{rung}"] == explicit[rung][0]
    assert said["room_bytes"] == 0
    assert said["budget_bytes"] == explicit[fits][0]
    assert _step_compilations() - before == said["compiled"]
    # the second step compiles nothing, and the rung's mathematics
    # are the named policy's
    state, _ = acc.train_step(state, _batch(acc))
    assert _step_compilations() - before == said["compiled"]
    assert len(_ladders()) == 1
    assert float(metrics["loss"]) == explicit[fits][2]
    # what .lower gives after the choice is the chosen rung's program
    text = acc.train_step.lower(state, batch).as_text()
    assert text == explicit[fits][1]
    assert len(_ladders()) == 1


def test_a_step_that_fits_at_the_top_pays_one_compilation(explicit, limit):
    trace.watch_compiles()
    limit(10 * explicit["none"][0])
    acc = _build(AUTO)
    state, batch = _state(acc), _batch(acc)
    before = _step_compilations()
    acc.train_step(state, batch)
    (said,) = _ladders()
    assert (said["rung"], said["compiled"]) == ("none", 1)
    assert said["room_bytes"] == 9 * explicit["none"][0]
    assert _step_compilations() - before == 1


def test_nothing_fits_and_the_bottom_rung_is_kept(explicit, limit):
    limit(explicit["full"][0] - 1)
    acc = _build(AUTO)
    acc.train_step(_state(acc), _batch(acc))
    (said,) = _ladders()
    assert said["rung"] == "full" and said["room_bytes"] == -1


@pytest.mark.parametrize("rung", RUNGS)
def test_an_explicit_policy_is_obeyed_and_no_ladder_runs(
        explicit, limit, rung):
    """Room for the top rung, and the user named another: theirs."""
    trace.watch_compiles()
    limit(10 * explicit["none"][0])
    acc = _build(_explicit(rung))
    state, batch = _state(acc), _batch(acc)
    before = _step_compilations()
    state, metrics = acc.train_step(state, batch)
    assert acc.train_step.rung is None and not _ladders()
    assert _step_compilations() - before == 1
    assert acc.train_step.lower(state, batch).as_text() == explicit[rung][1]
    assert float(metrics["loss"]) == explicit[rung][2]


def test_one_step_agrees_across_all_rungs(explicit):
    """Kept or recomputed, an activation is the same float32 array."""
    _, _, loss, grad_norm, grads = explicit["full"]
    for rung in RUNGS[:-1]:
        assert explicit[rung][2] == loss
        np.testing.assert_allclose(explicit[rung][3], grad_norm, rtol=1e-6)
        jax.tree_util.tree_map(  # to a few ulps of a leaf's largest
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=0, atol=1e-6 * np.abs(b).max()),
            explicit[rung][4], grads,
        )


def test_a_rebuilt_job_chooses_again(explicit, limit):
    """`ElasticTrainer` calls `accelerate()` after every change of the
    world: less room, a lower rung; more room, a higher one."""
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer

    limit(explicit["none"][0])
    et = ElasticTrainer(
        lambda k: llama.init_params(AUTO, k),
        lambda p, b, m: llama.loss_fn(AUTO, p, b, mesh=m),
        llama.partition_rules(AUTO), optax.adamw(1e-3),
        global_batch_size=4, max_per_replica_batch=4,
        mesh_spec=MeshSpec.fit(1), devices=jax.devices()[:1],
    )
    tokens = np.ones((4, 65), np.int32)
    state = et.init_state(jax.random.PRNGKey(0))
    state, _ = et.step(state, {"tokens": tokens})
    assert et.acc.train_step.rung == "none"
    limit(explicit["full"][0])
    state = et.on_world_change(state)
    state, _ = et.step(state, {"tokens": tokens})
    assert et.acc.train_step.rung == "full"
    limit(explicit["proj_mlp"][0])
    state = et.on_world_change(state)
    et.step(state, {"tokens": tokens})
    assert [said["rung"] for said in _ladders()] == [
        "none", "full", "proj_mlp"]


@pytest.mark.parametrize("refusal,kept", [
    ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
     "memory in memory space hbm.", "proj_mlp"),
    ("INTERNAL: Mosaic failed to compile TPU kernel", None),
])
def test_a_compile_refused_for_memory_is_a_rung_that_does_not_fit(
        explicit, limit, monkeypatch, refusal, kept):
    limit(10 * explicit["none"][0])
    acc = _build(AUTO)
    compile_ = jax.stages.Lowered.compile
    calls = []

    def refusing(self, *a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise jax.errors.JaxRuntimeError(refusal)
        return compile_(self, *a, **kw)

    monkeypatch.setattr(jax.stages.Lowered, "compile", refusing)
    if kept is None:  # any other failure is the caller's to see
        with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
            acc.train_step(_state(acc), _batch(acc))
        return
    acc.train_step(_state(acc), _batch(acc))
    (said,) = _ladders()
    assert said["rung"] == kept and said["peak_none"] == -1
    assert said["compiled"] == 2
    assert "none refused, proj_mlp 0.00 GB" in remat.ladder_summary()


def test_lower_on_avals_chooses_and_the_step_finds_the_choice(
        explicit, limit):
    """`auto_engine.DryRunner` and `profile_program` lower on avals
    before any step has run."""
    from dlrover_tpu.utils.program_stats import abstractify

    limit(explicit["proj_mlp"][0])
    acc = _build(AUTO)
    state, batch = _state(acc), _batch(acc)
    lowered = acc.train_step.lower(*abstractify((state, batch)))
    assert acc.train_step.rung == "proj_mlp"
    assert lowered.as_text() == explicit["proj_mlp"][1]
    acc.train_step(state, batch)
    assert len(_ladders()) == 1
    stats = acc.profile_program(state, batch)
    assert stats.peak_hbm_bytes == explicit["proj_mlp"][0]
    # a batch of another shape compiles at the kept rung, as under
    # `jax.jit`: the rung is chosen once an `accelerate()`
    _, metrics = acc.train_step(_state(acc), _batch(acc, rows=2))
    assert len(_ladders()) == 1 and np.isfinite(float(metrics["loss"]))


def test_the_ladder_under_a_mesh_and_under_accumulation(limit):
    limit(10**9)
    acc = _build(
        AUTO, Strategy(mesh=MeshSpec(fsdp=2), grad_accum=2),
        jax.devices()[:2],
    )
    from dlrover_tpu.utils.program_stats import abstractify

    state = _state(acc)
    batch = acc.shard_batch({"tokens": np.ones((2, 4, 65), np.int32)})
    acc.train_step.lower(*abstractify((state, batch)))
    state, metrics = acc.train_step(state, batch)
    (said,) = _ladders()
    assert said["rung"] == "none" and np.isfinite(float(metrics["loss"]))


def test_the_limit_is_read_from_a_device_this_process_owns(monkeypatch):
    """In a job of several processes the mesh is laid over every
    process's devices, and its first is its owner's alone: asked
    anywhere else it states nothing. A process that read the limit
    there would run "full" beside an owner that keeps "none": two
    programs in one job."""
    first, own = jax.devices()[:2]
    monkeypatch.setattr(
        Mesh, "local_devices",
        property(lambda self: [d for d in self.devices.flat if d != first]),
    )
    monkeypatch.setattr(
        accelerate_mod, "device_memory_bytes",
        lambda device=None: 0.0 if device == first else 1e9,
    )
    acc = _build(AUTO, Strategy(mesh=MeshSpec(fsdp=2)), [first, own])
    assert acc.mesh.devices.flat[0] == first
    assert isinstance(acc.train_step, remat.LadderStep)


@pytest.mark.parametrize("fits", RUNGS[1:])
@pytest.mark.parametrize("whole_loss", ["dots", "full"])
def test_under_a_remat_of_the_whole_loss_every_rung_is_traced_anew(
        limit, whole_loss, fits):
    """`Strategy.remat` wraps the whole loss in a `jax.checkpoint`,
    which keeps its traces by function and avals as `jax.jit` does:
    one wrap shared by the rungs would trace the top rung once and
    run it under every rung's name."""

    def strategy():
        return Strategy(mesh=MeshSpec.fit(1), remat=whole_loss)

    named = {}
    for rung in RUNGS:
        acc = _build(_explicit(rung), strategy())
        lowered = acc.train_step.lower(_state(acc), _batch(acc))
        named[rung] = (
            extract_program_stats(lowered.compile()).peak_hbm_bytes,
            lowered.as_text(),
        )
    assert len({text for _, text in named.values()}) == len(RUNGS)
    above = RUNGS[: RUNGS.index(fits)]
    assert all(named[rung][0] > named[fits][0] for rung in above)
    limit(named[fits][0])
    acc = _build(AUTO, strategy())
    state, batch = _state(acc), _batch(acc)
    state, metrics = acc.train_step(state, batch)
    (said,) = _ladders()
    assert said["rung"] == fits and np.isfinite(float(metrics["loss"]))
    for rung in above + (fits,):
        assert said[f"peak_{rung}"] == named[rung][0]
    assert acc.train_step.lower(state, batch).as_text() == named[fits][1]


class _Refused:
    """A rung's jitted step whose every run fails with `error`."""

    def __init__(self, step, error, before=lambda args: None):
        self.trace, self.lower = step.trace, step.lower
        self._error, self._before = error, before

    def __call__(self, *args):
        self._before(args)
        raise jax.errors.JaxRuntimeError(self._error)


OUT_OF_MEMORY = (
    "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
    "allocate 1.20G. That was not possible. There are 774.5M free."
)


@pytest.mark.parametrize("error,kept,case", [
    (OUT_OF_MEMORY, "proj_mlp", "one process, arguments whole"),
    ("INTERNAL: the chip halted", None, "another failure"),
    (OUT_OF_MEMORY, None, "several processes"),
    (OUT_OF_MEMORY, None, "the state was donated"),
    (OUT_OF_MEMORY, None, "the bottom rung"),
], ids=lambda x: x.replace(" ", "_") if x and " " in x and len(x) < 40 else "")
def test_a_first_run_refused_for_memory_is_a_rung_that_does_not_fit(
        explicit, limit, monkeypatch, case, error, kept):
    """The compiler judges the program alone; what the process holds
    beside it shows when the step first runs. The ladder goes on
    below the refused rung where it safely can, and raises on what it
    was given where it cannot."""
    refused = "full" if case == "the bottom rung" else "none"
    limit(explicit[refused][0])
    acc = _build(AUTO)
    step_at = acc.train_step._step_at

    def donated(args):
        args[0]["step"].delete()

    def refusing(rung):
        if rung != refused:
            return step_at(rung)
        return _Refused(
            step_at(rung), error,
            donated if case == "the state was donated" else lambda a: None,
        )

    acc.train_step._step_at = refusing
    state, batch = _state(acc), _batch(acc)
    if case == "several processes":
        monkeypatch.setattr(jax, "process_count", lambda: 2)
    if kept is None:
        with pytest.raises(jax.errors.JaxRuntimeError, match=error[:8]):
            acc.train_step(state, batch)
        assert len(_ladders()) == 1
        return
    state, metrics = acc.train_step(state, batch)
    first, second = _ladders()
    assert first["rung"] == "none" and "run_none" not in first
    assert (second["rung"], second["run_none"], second["compiled"]) == (
        kept, -1, 2)
    assert second["peak_none"] == explicit["none"][0]
    assert float(metrics["loss"]) == explicit[kept][2]
    assert acc.train_step.rung == kept
    assert (
        "none 0.01 GB and refused at its first run, proj_mlp 0.00 GB"
        in remat.ladder_summary()
    )
    # proven once, the step is the kept rung's jitted function
    acc.train_step(state, batch)
    assert len(_ladders()) == 2


def test_the_rung_is_read_where_it_is_traced():
    asked = remat.asked()
    assert remat.scan_policy("proj") == "proj" and remat.asked() == asked
    assert remat.scan_policy("auto") == "full"
    with remat.tracing_at("proj_mlp"):
        assert remat.scan_policy("auto") == "proj_mlp"
        with remat.tracing_at("none"):
            assert remat.scan_policy("auto") == "none"
        assert remat.scan_policy("dots") == "dots"
    assert remat.scan_policy("auto") == "full"
    assert remat.asked() == asked + 4
    with pytest.raises(ValueError, match="unknown remat policy"):
        remat.resolve_policy("auto")  # a rung's name, never "auto"


def test_the_choice_is_on_the_trainers_start_up_line(
        explicit, limit, caplog):
    from dlrover_tpu.common.log import default_logger
    from dlrover_tpu.trainer.trainer import Trainer

    trace.watch_compiles()
    limit(explicit["proj_mlp"][0])
    acc = _build(AUTO)
    acc.train_step(_state(acc), _batch(acc))
    default_logger.addHandler(caplog.handler)  # it does not propagate
    try:
        with caplog.at_level("INFO"):
            Trainer._log_startup()
    finally:
        default_logger.removeHandler(caplog.handler)
    (line,) = [
        r.getMessage() for r in caplog.records
        if r.getMessage().startswith("worker start-up")
    ]
    assert line.endswith(
        "; layer scans keep 'proj_mlp' (none 0.01 GB, proj_mlp 0.00 GB; "
        "budget 0.00 GB)")
