"""The save-kill-resume job (traffic/train_save_kill_resume.json) is
out of the manifest but kept ready: this drives it end to end at the
rehearsal's sizes through elastic_run -> agent -> worker, SIGKILL,
respawn and resume (about half a minute; it starts processes)."""

import argparse
import os
import time

import lib


def test_save_kill_resume_rehearsal():
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    manifest["end_to_end"].append(
        {"name": "resume_s", "unit": "s", "workloads": ["elastic"]})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "train_tokens_per_s":
            metric["workloads"].append("elastic")
    cell = lib.fill_cell(manifest, {
        "name": "elastic", "config": "mistral-7b-v0.3.train-1chip",
        "traffic": "train_save_kill_resume", "chips": 1, "why": "test",
    })
    train = lib.load_driver("train")
    args = argparse.Namespace(
        rehearsal=True, seed=2 ** 31 + 21, seconds=1.0, trace=0,
        keep_trace="", t_start=time.time())
    out = train.run(cell, args, args.t_start)
    assert out["correct"] and out["failed"] == 0
    assert set(out["metrics"]) == {
        "setup_s", "train_tokens_per_s", "resume_s"}
    assert out["metrics"]["resume_s"]["value"] > 0
    assert out["attempted"] >= 64 + 8 + 1
