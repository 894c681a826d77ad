"""The one general generator of inputs: training batches and serving
requests, from --seed and a traffic file's parameters. numpy only, so
that the load generator can import it without touching the chip.

Every seed is given the same amount of work. A training batch is
`batch(seed, step)`: all rows differ, every step differs. A serving
mix fixes its SET of (prompt, output) sizes once, from the mix's own
`sizes_seed`. How the set is dealt to the clients is the mix's `deal`:
absent, --seed deals it in another order and cuts every client's
first request, so two seeds differ in order and not in load;
"fixed_order" takes the order from `sizes_seed` too and cuts only the
requests that start in a slot, so two seeds differ in token ids (and
weights) and in nothing the clock can see.
"""

import math

import numpy as np

_MASK = (1 << 63) - 1


def _rng(*words) -> np.random.Generator:
    """A generator keyed by whole numbers of any size (--seed is a
    little over 2**31 at most, more than 32 signed bits hold)."""
    return np.random.default_rng([int(w) & _MASK for w in words])


def batch(seed: int, step: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    """Tokens [rows, seq + 1] (inputs and shifted targets) of one
    training step."""
    return _rng(seed, 1, step).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32
    )


def _log_uniform_quantiles(lo: int, hi: int, n: int, rng) -> np.ndarray:
    """n sizes whose logs are evenly spread over [log lo, log hi] (a
    stratified sample: one draw inside each of n equal strata)."""
    u = (np.arange(n) + rng.random(n)) / n
    return np.clip(
        np.rint(np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))),
        lo, hi,
    ).astype(np.int64)


def request_sizes(mix: dict) -> list:
    """The mix's fixed set of (prompt_tokens, output_tokens) pairs:
    clients x requests_per_client of them, the same for every seed."""
    n = mix["clients"] * mix["requests_per_client"]
    rng = _rng(mix["sizes_seed"], 2)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for spec in (p, o):
        if spec["distribution"] != "log_uniform":
            raise ValueError(f"unknown distribution {spec['distribution']!r}")
    prompts = _log_uniform_quantiles(p["min"], p["max"], n, rng)
    outputs = _log_uniform_quantiles(o["min"], o["max"], n, rng)
    rng.shuffle(outputs)  # prompt and output lengths are independent
    return [(int(a), int(b)) for a, b in zip(prompts, outputs)]


DEALS = ("by_seed", "fixed_order")


def client_requests(seed: int, mix: dict, vocab: int, slots: int = None) -> list:
    """What each client sends, in order: a list per client of
    {"tokens": [...], "max_new": n}. A closed loop in steady state
    finds each slot part-way through a request, so a FIRST request
    has its output cut to a share of its length, the shares spread
    evenly over (0, 1): the window then opens on a batch whose slots
    already end at different times.

    The mix's `deal` says whose and in what order. Absent (by_seed):
    --seed permutes the set of sizes and every client's first request
    is cut, the ones that only wait in the queue too. "fixed_order":
    the permutation and the shares come from `sizes_seed`, so every
    run of the cell gives client c the same sizes in the same order,
    and only clients 0 .. slots-1, which loadgen.py starts first and
    which therefore start in a slot, are cut; the clients that wait
    in the queue send whole requests. --seed draws every token id in
    both."""
    deal = mix.get("deal", "by_seed")
    if deal not in DEALS:
        raise ValueError(f"unknown deal {deal!r}")
    sizes = request_sizes(mix)
    rng = _rng(seed, 3)
    clients = mix["clients"]
    if deal == "fixed_order":
        if not slots or slots > clients:
            raise ValueError(
                f"deal fixed_order cuts the first {slots!r} clients' "
                f"requests: give the configuration's run.n_slots")
        order_rng = _rng(mix["sizes_seed"], 6)
        order = order_rng.permutation(len(sizes))
        head_share = (order_rng.permutation(slots) + 0.5) / slots
    else:
        order = rng.permutation(len(sizes))
        head_share = (rng.permutation(clients) + 0.5) / clients
    out = [[] for _ in range(clients)]
    for k, idx in enumerate(order):
        c = k % clients
        prompt_len, max_new = sizes[idx]
        if not out[c] and c < len(head_share):
            max_new = max(2, int(math.ceil(max_new * head_share[c])))
        tokens = rng.integers(1, vocab, size=prompt_len, dtype=np.int64)
        out[c].append({"tokens": tokens.tolist(), "max_new": int(max_new)})
    return out


def cut_clients(mix: dict, slots: int) -> int:
    """How many clients, counted from client 0, send a cut first
    request: all of them, or the slots' under deal fixed_order."""
    return slots if mix.get("deal") == "fixed_order" else mix["clients"]


def warm_requests(seed: int, mix: dict, vocab: int) -> list:
    """One short request per prefill bucket the mix's prompts touch;
    its output length walks the engine through every chunk length."""
    rng = _rng(seed, 4)
    return [
        {
            "tokens": rng.integers(1, vocab, size=n, dtype=np.int64).tolist(),
            "max_new": int(mix["warm_output_tokens"]),
        }
        for n in mix["warm_prompt_tokens"]
    ]


def sample_indices(seed: int, n: int, k: int, always: int) -> list:
    """k of range(n), drawn from the seed, with `always` among them."""
    if n <= k:
        return list(range(n))
    rest = [i for i in range(n) if i != always]
    picked = _rng(seed, 5).choice(len(rest), size=k - 1, replace=False)
    return [always] + [rest[i] for i in sorted(picked)]
