"""An admission issues no device-to-host fetch, in any branch.

`_admit` enqueues the prompt's forward and the state scatters and
returns. A fetch inside it (the request's key used to be split on the
device and fetched: `np.asarray(sub)`) waits for everything queued
before it, the admission's own prefill included, so the next
admission, the rings and the chunk's enqueue are prepared with the
device idle (DEVIATIONS §9; PERF.md §6, PR 36).

`jax.transfer_guard` sees nothing on the CPU backend (a CPU array IS
host memory), so the guard here is a spy with two halves. `int()`,
`bool()`, `.tolist()` and `jax.device_get` of a jax array go through
`ArrayImpl._value`, and `.item()` is a method beside it: both are
patched. `np.asarray` and `np.array` take a CPU array by the buffer
protocol and never reach either, so the serving modules' `np` is
replaced by a proxy whose converting functions look at what they are
handed. The last test holds the spy to the fetch that was there.
"""

import contextlib
import dataclasses
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as jax_array

import _mellum2_tiny as tiny
from dlrover_tpu.models import llama
from dlrover_tpu.serving import engine as engine_mod
from dlrover_tpu.serving import handoff, kv_tier, paged_kv
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.replica import InferenceReplica, ReplicaPool
from dlrover_tpu.serving.scheduler import RequestScheduler, SloConfig


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def hybrid():
    m = tiny.model_dict(n_layers=4, window=8)
    return tiny.config(m), tiny.params(m, seed=3)


def _prompts(lengths, seed=0, shared_prefix=0):
    rng = np.random.default_rng(seed)
    base = rng.integers(1, 120, size=shared_prefix).tolist()
    return [base + rng.integers(1, 120, size=n).tolist() for n in lengths]


_CONVERTERS = ("asarray", "array", "copy", "asanyarray", "ascontiguousarray")


class _NumpySpy:
    """numpy, but its converting functions tell `note` when they are
    handed a jax array."""

    def __init__(self, note):
        self._note = note

    def __getattr__(self, name):
        fn = getattr(np, name)
        if name not in _CONVERTERS:
            return fn

        def spied(a, *args, **kw):
            if isinstance(a, jax.Array):
                self._note()
            return fn(a, *args, **kw)

        return spied


@contextlib.contextmanager
def fetch_spy(monkeypatch, *engines):
    """Records every materialization of a jax array that happens while
    one of `engines` is inside `_admit`: the admissions counted, and
    for each fetch the lines that asked for it."""
    real_value = jax_array.ArrayImpl._value
    real_item = jax_array.ArrayImpl.item
    seen = {"admits": 0, "inside": 0, "fetches": []}

    def note():
        if seen["inside"]:
            seen["fetches"].append(
                "".join(traceback.format_stack(limit=7)[:-2])
            )

    def value(self):
        note()
        return real_value.fget(self)

    def item(self, *args):
        note()
        return real_item(self, *args)

    def wrap(fn):
        def admit(slot, req):
            seen["admits"] += 1
            seen["inside"] += 1
            try:
                return fn(slot, req)
            finally:
                seen["inside"] -= 1
        return admit

    with monkeypatch.context() as mp:
        mp.setattr(jax_array.ArrayImpl, "_value", property(value))
        mp.setattr(jax_array.ArrayImpl, "item", item)
        for module in (engine_mod, handoff, paged_kv, kv_tier):
            mp.setattr(module, "np", _NumpySpy(note))
        for eng in engines:
            mp.setattr(eng, "_admit", wrap(eng._admit))
        yield seen


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("chunk", 2)
    kw.setdefault("pad_id", -1)
    return ContinuousBatcher(cfg, params, **kw)


# one entry a branch of `_admit`; each with a temperature, so that the
# engine has to deal every request a key (the fetch that was there)
BRANCHES = [
    ("dense", dict()),
    ("paged", dict(kv_layout="paged")),
    ("dense-prefix", dict(prefix_cache_rows=4, prefix_block=8)),
    ("paged-prefix", dict(
        kv_layout="paged", prefix_cache_rows=4, prefix_block=8)),
    ("dense-chunked", dict(prefill_chunk=4)),
    ("paged-chunked", dict(kv_layout="paged", prefill_chunk=4)),
    ("paged-chunked-prefix", dict(
        kv_layout="paged", prefill_chunk=4, prefix_cache_rows=4,
        prefix_block=8)),
    ("paged-int8-spec", dict(
        kv_layout="paged", kv_quant=True, spec_draft_len=3)),
]


@pytest.mark.parametrize(
    "kw", [b[1] for b in BRANCHES], ids=[b[0] for b in BRANCHES]
)
def test_no_admission_fetches_from_the_device(model, monkeypatch, kw):
    cfg, params = model
    eng = _engine(cfg, params, temperature=0.8, top_k=20, seed=4, **kw)
    # a shared prefix of two blocks: with a prefix cache the later
    # admissions take the warm and the full-hit branches too
    prompts = _prompts((3, 9, 1, 6, 12), seed=1, shared_prefix=16)
    prompts.append(list(prompts[0][:16]))  # a full hit, block aligned
    with fetch_spy(monkeypatch, eng) as seen:
        outs = eng.generate_all(prompts)
    assert seen["admits"] >= len(prompts)
    assert not seen["fetches"], "\n".join(seen["fetches"])
    assert all(len(o) > 0 for o in outs)
    if kw.get("prefix_cache_rows") and not kw.get("prefill_chunk"):
        # (a chunked admission never publishes a prefix, so never hits)
        assert eng.prefix_cache.hits > 0


def test_no_hybrid_admission_fetches_from_the_device(hybrid, monkeypatch):
    cfg, params = hybrid
    eng = _engine(
        cfg, params, n_slots=3, max_new_tokens=12, chunk=4,
        kv_layout="paged", page_size=4, temperature=0.8, seed=2,
    )
    prompts = _prompts((5, 19, 30, 12), seed=0)
    with fetch_spy(monkeypatch, eng) as seen:
        eng.generate_all(prompts)
    assert seen["admits"] == len(prompts)
    assert not seen["fetches"], "\n".join(seen["fetches"])


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_no_adopted_admission_fetches_from_the_device(
    model, monkeypatch, layout
):
    """A decode replica installs KV another replica prefilled and
    shipped device to device (serving/handoff.py)."""
    cfg, params = model
    metrics = ServingMetrics()
    pool = ReplicaPool(metrics=metrics)
    scheds = []
    for role in ("prefill", "decode"):
        eng = _engine(
            cfg, params, n_slots=3, temperature=0.8, seed=7,
            kv_layout=layout, replica_role=role,
        )
        sch = RequestScheduler(
            eng, SloConfig(), metrics=metrics, handoff_transport="device"
        )
        pool.add(InferenceReplica(role, sch))
        scheds.append(sch)
    decode_eng = scheds[1].engine
    with fetch_spy(monkeypatch, decode_eng) as seen:
        reqs = [pool.submit(p, max_new=4) for p in _prompts((5, 9, 3))]
        for _ in range(10_000):
            if not any([s.pump() for s in scheds]):
                break
    assert all(r.state.value == "done" for r in reqs)
    assert seen["admits"] == len(reqs)
    assert not seen["fetches"], "\n".join(seen["fetches"])


def test_the_spy_catches_the_fetch_that_was_there(model, monkeypatch):
    """What `_admit` did before PR 36: split the engine's key on the
    device and fetch the half. The spy has to see it, or the tests
    above prove nothing."""
    cfg, params = model
    eng = _engine(cfg, params, temperature=0.8, seed=4)

    class DeviceSplit:
        # the parent's two lines, under the name `_admit` now calls
        @staticmethod
        def split(key):
            new, sub = jax.random.split(jnp.asarray(key))
            return engine_mod.np.asarray(new), engine_mod.np.asarray(sub)

    with fetch_spy(monkeypatch, eng) as seen:
        monkeypatch.setattr(engine_mod, "host_prng", DeviceSplit)
        eng.generate_all(_prompts((3, 9)))
    assert seen["admits"] == 2
    assert len(seen["fetches"]) == 4  # two halves an admission
    assert all("in split" in f for f in seen["fetches"])
    # and the other half of the spy: a number read off a device array
    with fetch_spy(monkeypatch, eng) as seen:
        monkeypatch.setattr(
            eng, "_prompt_bucket",
            lambda p: int(jnp.asarray(16)) + jnp.asarray(0).item(),
        )
        eng.generate_all(_prompts((3,)))
    assert seen["admits"] == 1 and len(seen["fetches"]) >= 2
