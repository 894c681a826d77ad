"""The keys the serving engine deals its requests come off the
engine's key on the HOST (serving/host_prng.py), so that an admission
never waits for the device. They have to be the keys the device would
have dealt: `jax.random.split` bit for bit, and request for request
the sequence `self.key, sub = jax.random.split(self.key)` gave before
the split moved."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.serving import host_prng
from dlrover_tpu.serving.engine import ContinuousBatcher


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(params=[True, False], ids=["partitionable", "original"])
def threefry_flag(request):
    """jax derives a split's counters in one of two ways; the host
    follows whichever the process is set to."""
    before = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", request.param)
    yield request.param
    jax.config.update("jax_threefry_partitionable", before)


def _device_chain(key, n):
    """What the engine did before: n requests' keys, split on the
    device one after another."""
    out = []
    for _ in range(n):
        key, sub = jax.random.split(key)
        out.append(np.asarray(sub, np.uint32))
    return out


def test_split_is_jax_random_split_bit_for_bit(threefry_flag):
    rng = np.random.default_rng(0)
    keys = [np.asarray(jax.random.PRNGKey(s)) for s in (0, 1, 7, 2**31 - 1)]
    keys += [
        rng.integers(0, 2**32, 2, dtype=np.uint64).astype(np.uint32)
        for _ in range(60)
    ]
    keys += [np.zeros(2, np.uint32), np.full(2, 0xFFFFFFFF, np.uint32)]
    for key in keys:
        want = np.asarray(jax.random.split(jnp.asarray(key)))
        new, sub = host_prng.split(key)
        assert new.dtype == sub.dtype == np.uint32
        assert new.tolist() == want[0].tolist(), key
        assert sub.tolist() == want[1].tolist(), key


def test_a_chain_of_splits_follows_the_devices(threefry_flag):
    key = np.asarray(jax.random.PRNGKey(1234))
    want = _device_chain(jax.random.PRNGKey(1234), 12)
    for w in want:
        key, sub = host_prng.split(key)
        assert sub.tolist() == w.tolist()


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("seed", [0, 5, 2147483647])
def test_requests_keys_are_the_parents_element_for_element(
    model, layout, seed
):
    """A fixed seed and order of admissions: each request's key is
    what the device-side split dealt it before PR 36."""
    cfg, params = model
    eng = ContinuousBatcher(
        cfg, params, n_slots=2, max_len=64, max_new_tokens=4, chunk=2,
        pad_id=-1, temperature=0.8, top_k=20, seed=seed, kv_layout=layout,
    )
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 250, size=n).tolist() for n in (5, 9, 3, 7, 4)]
    ids = [eng.submit(p) for p in prompts]
    reqs = [eng._requests[i] for i in ids]
    # two slots, FIFO queue: admitted in submission order
    eng.generate_all([])
    want = _device_chain(jax.random.PRNGKey(seed), len(prompts))
    assert [r.prng_key.tolist() for r in reqs] == [w.tolist() for w in want]
    assert all(r.prng_key.dtype == np.uint32 for r in reqs)
    # the engine's own key went down the same chain
    key = jax.random.PRNGKey(seed)
    for _ in prompts:
        key, _ = jax.random.split(key)
    assert eng.key.tolist() == np.asarray(key).tolist()


def test_a_pinned_key_draws_nothing_from_the_engines(model):
    cfg, params = model
    eng = ContinuousBatcher(
        cfg, params, n_slots=2, max_len=64, max_new_tokens=4, chunk=2,
        pad_id=-1, temperature=0.8, seed=3,
    )
    before = eng.key.copy()
    pinned = np.asarray([11, 12], np.uint32)
    idx = eng.submit([5, 6, 7], prng_key=pinned)
    req = eng._requests[idx]
    eng.generate_all([])
    assert req.prng_key.tolist() == pinned.tolist()
    assert eng.key.tolist() == before.tolist()


def test_setting_the_key_takes_a_device_key_to_the_host(model):
    """The PPO rollout re-keys its engine with a jax key before every
    drain (rl/ppo.py): the setter is the one fetch, and the requests'
    keys then follow that key's chain."""
    cfg, params = model
    eng = ContinuousBatcher(
        cfg, params, n_slots=2, max_len=64, max_new_tokens=3, chunk=2,
        pad_id=-1, temperature=0.8, seed=0,
    )
    eng.key = jax.random.PRNGKey(42)
    assert isinstance(eng.key, np.ndarray) and eng.key.dtype == np.uint32
    ids = [eng.submit(p) for p in ([1, 2, 3], [4, 5], [6, 7, 8, 9])]
    reqs = [eng._requests[i] for i in ids]
    eng.generate_all([])
    want = _device_chain(jax.random.PRNGKey(42), 3)
    assert [r.prng_key.tolist() for r in reqs] == [w.tolist() for w in want]
