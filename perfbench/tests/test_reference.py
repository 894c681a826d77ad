"""reference.py against the program's own loss at tiny sizes in
float32 (where the two are the same mathematics), and the control:
the reference at fp8 in the program's place comes out NOT correct
under the configuration's own limits."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

import generate
import lib
import reference
import weights

SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def tiny():
    full = lib.read_json(
        f"{lib.BENCH}/configs/mistral-7b-v0.3.train-1chip.json")
    return full, weights.tiny_model(full)


def test_loss_equals_the_programs_at_float32(tiny):
    from dlrover_tpu.models import llama

    _, model = tiny
    params = weights.make_params(model, SEED, "float32")
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32,
        attn_impl="reference", rope_theta=model["rope_theta"],
        norm_eps=model["rms_norm_eps"],
    )
    tokens = jnp.asarray(generate.batch(SEED, 1, 2, 64, model["vocab_size"]))
    ours = float(reference.loss(model, params, tokens))
    theirs = float(llama.loss_fn(cfg, params, {"tokens": tokens})[0])
    assert ours == pytest.approx(theirs, rel=1e-6)


def test_weights_repeat_for_a_seed(tiny):
    _, model = tiny
    a = weights.make_params(model, SEED, "float32")
    b = weights.make_params(model, SEED, "float32")
    c = weights.make_params(model, SEED + 1, "float32")
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((a["layers"]["wq"] == c["layers"]["wq"]).all())


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_fp8_control_is_not_correct(tiny, seed):
    full, model = tiny
    train = lib.load_driver("train")
    limits = full["limits"]
    batches = [generate.batch(seed, s, 2, 64, model["vocab_size"])
               for s in (1, 2, 3)]
    ref = reference.train_steps(model, seed, batches, 3e-4)
    control = reference.train_steps(model, seed, batches, 3e-4, "fp8")
    numbers = train.compare_with_reference(control, ref)
    assert numbers["grad_norm_worst_leaf"] > 3 * limits[
        "grad_norm_worst_leaf"]["limit"]
    # and the reference against itself is exact
    again = reference.train_steps(model, seed, batches, 3e-4)
    assert max(train.compare_with_reference(again, ref).values()) == 0.0


def test_served_gaps_and_their_control(tiny):
    _, model = tiny
    params = weights.make_params(model, SEED, "float32")
    prompt = list(range(1, 20))
    logits = reference.forward(model, params, jnp.asarray([prompt]))
    greedy = []
    for _ in range(6):  # the reference's own greedy continuation
        logits = reference.forward(
            model, params, jnp.asarray([prompt + greedy]))
        greedy.append(int(jnp.argmax(logits[0, -1])))
    gaps, control = reference.served_token_gaps(
        model, params, prompt, greedy, 64, "fp8")
    assert gaps.shape == (6,) and float(gaps.max()) == 0.0
    wrong = list(greedy)
    wrong[3] = (wrong[3] + 1) % model["vocab_size"]
    gaps, _ = reference.served_token_gaps(model, params, prompt, wrong, 64)
    assert float(gaps[3]) > 0.0 and float(gaps[:3].max()) == 0.0
    assert control is not None and control.shape == (6,)
