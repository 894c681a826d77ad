"""The control of `correct`, run by hand on the chip at a cell's own
size (the benchmark's runs never run it):

    python3 perfbench/control.py --config <name> --seeds 11,12,13

The control is the plain reference put in the program's place and
computed one precision below the one the configuration states (fp8
matmul operands for a configuration that states bf16). It must come
out as NOT correct under the same comparison and the same limits
that the program is held to. This prints, for every seed, the
numbers that comparison reads, for the control and (for information)
for the reference at the program's own stated precision.

Training configurations: three AdamW steps each (no window needed).
Serving configurations are read inside a serving run instead, where
the served tokens are: `run.py --control` (see drivers/serve.py).
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import generate  # noqa: E402
import lib  # noqa: E402
import weights  # noqa: E402


def train_control(model: dict, seeds, precisions, rehearsal: bool) -> list:
    import jax

    import reference
    train = lib.load_driver("train")

    run = model["run"]
    if rehearsal:
        model, run = weights.tiny_model(model), dict(run, seq=64, batch=2)
    rows = []
    for seed in seeds:
        batches = [
            generate.batch(seed, s, run["batch"], run["seq"],
                           model["vocab_size"])
            for s in (1, 2, 3)
        ]
        with jax.default_matmul_precision("highest"):
            ref = reference.train_steps(
                model, seed, batches, run["learning_rate"])
            for precision in precisions:
                t0 = time.time()
                low = reference.train_steps(
                    model, seed, batches, run["learning_rate"], precision)
                numbers = train.compare_with_reference(low, ref)
                rows.append((seed, precision, numbers))
                print("CONTROL " + json.dumps(dict(
                    numbers, seed=seed, precision=precision,
                    seconds=time.time() - t0)), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--precisions", default="fp8,bf16")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from dlrover_tpu.runtime import enable_compile_cache

    device = lib.require_device(args.rehearsal, chips=1)
    enable_compile_cache()
    model = lib.read_json(
        os.path.join(BENCH, "configs", args.config + ".json"))
    if model["driver"] != "train":
        raise SystemExit("serving configurations: use run.py --control")
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = train_control(
        model, seeds, args.precisions.split(","), args.rehearsal)
    train = lib.load_driver("train")
    for seed, precision, numbers in rows:
        failed = [
            name for name, value in numbers.items()
            if value > train.limit_of(model["limits"], name)
        ]
        print(f"seed {seed} {precision}: "
              f"{'NOT correct' if failed else 'passes as correct'} {failed}")
    print(json.dumps({"device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
