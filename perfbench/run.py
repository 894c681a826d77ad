"""The benchmark's one command:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json once in a new process: set-up,
warm-up, a measured window, a check of what the window produced
against the plain reference, and as the LAST line of stdout one JSON
object (correct, attempted, failed, metrics, device, in a traced run
breakdown, and last `checks`: each number compared beside its limit).
A platform other than `tpu`, or another number of chips than the cell
asks for, is a failure: exit code 1, no result line. `--rehearsal` runs the same control flow at tiny sizes on the
CPU and says so; it proves nothing about the chip.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import lib  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", default="",
                    help="serving cells: also read the control's gap at "
                    "this lower precision (fp8), printed on a CONTROL line")
    ap.add_argument("--keep-trace", default="",
                    help="copy the raw .xplane.pb of a traced run here")
    ap.add_argument("--dump", default="",
                    help="serving cells: write the raw records of the run "
                    "(requests, engine steps, submits) into this directory")
    args = ap.parse_args()
    args.t_start = T_START
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        cell = lib.load_cell(args.workload)
        driver = lib.load_driver(cell["model"]["driver"])
        result = driver.run(cell, args, T_START)
    except BaseException:  # noqa: BLE001 — reported, then exit != 0
        traceback.print_exc()
        sys.stderr.flush()
        lib.log("[perfbench] FAILED: no result")
        return 1
    if args.rehearsal:
        # a CPU's numbers are never written under a device metric's name
        lib.log("[rehearsal: CPU readings, not device numbers] "
                + json.dumps(result["metrics"]))
        for metric in result["metrics"].values():
            metric["value"] = None
        result.pop("breakdown", None)
        for key in ("busy_s", "window_s"):
            result["device"].pop(key, None)
    lib.log(f"[perfbench] {args.workload} seed {args.seed}: "
            f"{time.time() - T_START:.0f} s in all")
    # every number compared beside its limit: the last lines of
    # standard error, and the last key of the result line
    result["checks"] = result.pop("checks", {})
    for name, c in result["checks"].items():
        print(f"CHECK {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
