"""How many requests a closed-loop mix has to deal each client.

A client that has sent its last request sits idle, the loop is no
longer closed, and the run is not `correct` (the drivers hold
`clients_ran_out` to 0). The faster the program, the sooner a client
whose requests are short gets through them, so `requests_per_client`
is sized for the fastest program the chip allows, and not for the one
that is measured today: no client may run out at 1.5 times the cell's
ROOFLINE rate, which is the slots of the batch over the time the chip
needs to read what one decode step must read (the driver's
`decode_step_bytes` over peaks.json's HBM rate; a decode step at
these batch sizes is bound by bytes, not by operations).

`run_dry` is the model of the loop that says so, on the CPU and with
no clock: generate.client_requests' own deal, a slot that gives a
token every slots / rate seconds, a freed slot refilled at once from
a first-in-first-out queue, no cost of admission (which makes the
model harsher than any chip). A mix whose `deal` is "fixed_order"
deals every seed the same sizes in the same order, so the rule reads
that ONE deal; any other mix is read over --seeds deals. By hand, for
every closed-loop cell of BENCHMARK.json:

    python3 perfbench/closed_loop.py [--seeds 24]
"""

import argparse
import heapq
import os

import generate
import lib

DEVICE_KIND = "TPU v5 lite"
HEADROOM = 1.5    # times the roofline rate that no client may run out at
SLACK_S = 3.0     # the clients start that long before ramp_s begins, at most


def live_contexts(mix: dict) -> list:
    """(positions, share of slot time) of the mix's requests, the
    shares summing to 1: a request of p prompt and n output tokens
    holds its slot for n steps at p + n/2 positions on average."""
    sizes = generate.request_sizes(mix)
    steps = float(sum(n for _, n in sizes))
    return [(p + n / 2.0, n / steps) for p, n in sizes]


def roofline_tokens_per_s(cell: dict, device_kind: str = DEVICE_KIND) -> float:
    model, mix = cell["model"], cell["mix"]
    slots = model["run"]["n_slots"]
    driver = lib.load_driver(model["driver"])
    nbytes = driver.decode_step_bytes(model, slots, live_contexts(mix))
    return slots * lib.peaks_for(device_kind)["hbm_bytes_per_s"] / nbytes


def horizon_s(mix: dict, run_seconds: float) -> float:
    return mix["ramp_s"] + run_seconds + SLACK_S


def run_dry(outputs: list, slots: int, tokens_per_s: float, until_s: float):
    """`outputs`: per client, the output tokens of each of its
    requests in order. Returns (the clients that ended their last
    request before `until_s`, the fewest requests any client had left
    unsent then: loadgen.py's `clients_ran_out`, `least_requests_left`)."""
    per_token = slots / tokens_per_s
    sent = [1] * len(outputs)  # a request counts from when it is queued
    waiting = list(range(len(outputs)))  # first in, first out
    head, busy, now, ran_out = 0, [], 0.0, []
    while True:
        while len(busy) < slots and head < len(waiting):
            c = waiting[head]
            head += 1
            heapq.heappush(
                busy, (now + outputs[c][sent[c] - 1] * per_token, c))
        if not busy:  # every client is through
            break
        now, c = heapq.heappop(busy)
        if now >= until_s:
            break
        if sent[c] == len(outputs[c]):
            ran_out.append(c)
        else:
            sent[c] += 1
            waiting.append(c)
    return sorted(ran_out), min(len(o) - s for o, s in zip(outputs, sent))


def deal_outputs(seed: int, mix: dict, slots: int) -> list:
    return [[r["max_new"] for r in reqs]
            for reqs in generate.client_requests(seed, mix, 2, slots)]


def deals(mix: dict, slots: int, seeds: int) -> list:
    """The deals the rule reads: one where the seed does not move
    the sizes, `seeds` of them where it does."""
    if mix.get("deal") == "fixed_order":
        seeds = 1
    return [deal_outputs(2 ** 31 + s, mix, slots) for s in range(seeds)]


def closed_loop_cells(manifest: dict) -> list:
    cells = [lib.fill_cell(manifest, dict(w)) for w in manifest["workloads"]]
    return [c for c in cells if c["mix"].get("loop") == "closed"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=24)
    args = ap.parse_args()
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    for cell in closed_loop_cells(manifest):
        mix, slots = cell["mix"], cell["model"]["run"]["n_slots"]
        roof = roofline_tokens_per_s(cell)
        until = horizon_s(mix, manifest["run_seconds"])
        dealt = deals(mix, slots, args.seeds)
        lib.log(f"{cell['name']}: {mix['requests_per_client']} requests a "
                f"client, roofline {roof:.0f} tokens/s, {until:.0f} s, "
                f"deal {mix.get('deal', 'by_seed')}")
        for times in (0.25, 0.5, 1.0, HEADROOM, 2.0):
            runs = [run_dry(d, slots, times * roof, until) for d in dealt]
            lib.log(f"  at {times:4.2f} x roofline ({times * roof:6.0f} tokens/s): "
                    f"a client ran out in {sum(bool(r[0]) for r in runs)} of "
                    f"{len(runs)} deals, least_requests_left "
                    f"{min(r[1] for r in runs)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
