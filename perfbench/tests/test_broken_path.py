"""A run with the timed path broken underneath comes out with
`correct` false. The harness's look for a chip is skipped (the
rehearsal's sizes on the CPU); the rest of a run is driven."""

import argparse
import time

import jax

import generate
import lib
import reference
import weights


def test_a_train_step_that_returns_its_state_unchanged():
    import train_worker  # perfbench/drivers, on the path below

    train = lib.load_driver("train")
    cell = lib.load_cell("mistral7b_train_steady")
    args = argparse.Namespace(rehearsal=True, seed=2 ** 31 + 9)
    model, run, cfg, acc = train_worker.build(args, cell["model"])

    def events_of(step_fn):
        acc.train_step = step_fn
        state = acc.init(weights.seed_key(args.seed))
        feed = train_worker.Feed(
            acc, args.seed, run["batch"], run["seq"], model["vocab_size"], 1)
        trainer = train_worker.Trainer(acc, state, feed, 0)
        first = train_worker.first_steps(trainer, model, args.seed)
        batches = [
            generate.batch(
                args.seed, s, run["batch"], run["seq"], model["vocab_size"])
            for s in (1, 2, 3)
        ]
        ref = reference.train_steps(
            model, args.seed, batches, run["learning_rate"])
        return [
            {"event": "worker_up", "restart": 0},
            first,
            {"event": "window", "compilations": 0, "losses_nonfinite": 0,
             "saves": []},
            dict(ref, event="reference"),
        ]

    sound = acc.train_step
    assert train.judge(cell, events_of(sound), rehearsal=True).ok

    def frozen(state, batch):  # computes, and keeps the state it was given
        kept = jax.tree_util.tree_map(lambda x: x + 0, state)
        _, metrics = sound(state, batch)
        return kept, metrics

    frozen_checks = train.judge(cell, events_of(frozen), rehearsal=True)
    assert not frozen_checks.ok
    assert any(c["value"] > c["limit"] for c in frozen_checks.compared.values())


def test_a_served_token_altered_where_it_is_produced(monkeypatch):
    from dlrover_tpu.serving import engine

    serve = lib.load_driver("serve")
    cell = lib.load_cell("mistral7b_serve_decode")
    args = argparse.Namespace(
        rehearsal=True, seed=2 ** 31 + 9, seconds=2.0, trace=0,
        control="", keep_trace="", dump="")
    sound = serve.run(cell, args, time.time())
    assert sound["correct"] and sound["attempted"] > 0
    assert sound["failed"] == 0

    real_step = engine.ContinuousBatcher.step

    def altered(self):
        events = real_step(self)
        return [
            (idx, [(t + 1) % 256 for t in tokens], done)
            for idx, tokens, done in events
        ]

    monkeypatch.setattr(engine.ContinuousBatcher, "step", altered)
    broken = serve.run(cell, args, time.time())
    assert broken["attempted"] > 0 and not broken["correct"]
