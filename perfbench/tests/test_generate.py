"""The generator repeats exactly for a seed, differs across seeds,
and gives every seed the same amount of work; a mix without the key
`deal` is dealt what the generator before PR 52 dealt it, to the
byte; under "fixed_order" a seed moves the token ids and nothing
else."""

import hashlib
import json

import numpy as np
import pytest

import generate
import lib

BIG = 2 ** 31 + 11  # more than 32 signed bits hold


def test_batch_repeats_and_differs():
    a = generate.batch(BIG, 7, 2, 64, 32768)
    assert a.shape == (2, 65) and a.dtype == np.int32
    assert np.array_equal(a, generate.batch(BIG, 7, 2, 64, 32768))
    assert not np.array_equal(a, generate.batch(BIG, 8, 2, 64, 32768))
    assert not np.array_equal(a, generate.batch(BIG + 1, 7, 2, 64, 32768))
    assert not np.array_equal(a[0], a[1])  # rows that all differ
    assert 0 <= a.min() and a.max() < 32768


def test_requests_repeat_and_seeds_share_the_sizes():
    mix = lib.load_cell("mistral7b_serve_decode")["mix"]
    a = generate.client_requests(BIG, mix, 32768)
    assert a == generate.client_requests(BIG, mix, 32768)
    b = generate.client_requests(BIG + 1, mix, 32768)
    assert a != b
    assert len(a) == mix["clients"] == 144

    def sizes(clients):  # all but each client's cut first request
        return sorted(
            (len(r["tokens"]), r["max_new"])
            for reqs in clients for r in reqs[1:]
        )

    full = sorted(generate.request_sizes(mix))
    assert len(full) == 144 * mix["requests_per_client"] == 144 * 12
    prompts = [p for p, _ in full]
    outputs = [o for _, o in full]
    assert min(prompts) >= 64 and max(prompts) <= 512
    assert min(outputs) >= 128 and max(outputs) <= 768
    assert 200 < np.mean(prompts) < 230 and 340 < np.mean(outputs) < 375
    # the same multiset of prompt sizes for every seed
    assert sorted(len(r["tokens"]) for c in a for r in c) == \
        sorted(len(r["tokens"]) for c in b for r in c) == prompts
    assert len(sizes(a)) == len(sizes(b))
    # first requests are cut so that the slots end at different times
    firsts = [reqs[0]["max_new"] for reqs in a]
    assert len(set(firsts)) > 72


# sha256 of json.dumps(client_requests(seed, mix, vocab)) by the
# generator of the commit before PR 52 (c7e649f), which knew one deal
PARENTS_DEAL = {
    ("mistral7b_serve_decode", 32768, BIG):
        "3a093d908bc5f7cbd9a9b7f0e277709ed105f4f84318ed4db80fa302317c4c80",
    ("mistral7b_serve_decode", 32768, 7):
        "512b77549b1a3b2e29220eae41f0b10513513e1a1bd41070874e88426d74fc76",
    ("sdar_serve_block_diffusion", 151669, BIG):
        "0440e063ab54c17d1be7de91d97941756ffe6415c63e12dc2fc74e041e19fcd0",
    ("sdar_serve_block_diffusion", 151669, 7):
        "bb717ada990552f96b4bd6c5be69898fc87b3cf3d352e825c288247bb8256ec6",
}
# the same of the two mixes that took the key, as they were dealt
# BEFORE they took it: the key, and nothing else, moves their deal
PARENTS_DEAL_OF_THE_CHANGED = {
    ("mellum2_serve_context_decode", 98304, BIG):
        "64b2918b2ce7f58f7cde6f8d4ec1df8b5d2655d7465cca8587739abb732192a4",
    ("gigachat3_serve_latent_decode", 16032, BIG):
        "d66582685d8b7d0527f36fdce377e301d7be099f7b221aa4f434cb8b0d431183",
}
# sha256 of batch(seed, 3, 2, 2048, 32768).tobytes(), the same commit
PARENTS_BATCH = {
    BIG: "dea4a05ea675a20533b1b7cf81622a46afaa846930591f8bb7ebd26324deada0",
    7: "66134e85a9964294dcd20612f20d0099a1d56604823b4f834d1de22283b4fd53",
}
FIXED_ORDER = ("mellum2_serve_context_decode", "gigachat3_serve_latent_decode")


def digest(deal) -> str:
    return hashlib.sha256(json.dumps(deal).encode()).hexdigest()


@pytest.mark.parametrize("cell,vocab,seed", sorted(PARENTS_DEAL))
def test_a_mix_without_the_key_is_dealt_what_the_parent_dealt(cell, vocab, seed):
    mix = lib.load_cell(cell)["mix"]
    assert "deal" not in mix
    slots = lib.load_cell(cell)["model"]["run"]["n_slots"]
    want = PARENTS_DEAL[cell, vocab, seed]
    assert digest(generate.client_requests(seed, mix, vocab)) == want
    # the slots are handed over or not: such a mix takes no notice
    assert digest(generate.client_requests(seed, mix, vocab, slots)) == want
    assert generate.cut_clients(mix, slots) == mix["clients"]


@pytest.mark.parametrize("cell,vocab,seed", sorted(PARENTS_DEAL_OF_THE_CHANGED))
def test_the_key_alone_moves_the_changed_mixes_deal(cell, vocab, seed):
    mix = lib.load_cell(cell)["mix"]
    assert mix["deal"] == "fixed_order"
    without = {k: v for k, v in mix.items() if k != "deal"}
    assert digest(generate.client_requests(seed, without, vocab)) == \
        PARENTS_DEAL_OF_THE_CHANGED[cell, vocab, seed]


@pytest.mark.parametrize("seed", sorted(PARENTS_BATCH))
def test_a_training_batch_is_what_the_parent_drew(seed):
    a = generate.batch(seed, 3, 2, 2048, 32768)
    assert hashlib.sha256(a.tobytes()).hexdigest() == PARENTS_BATCH[seed]


@pytest.mark.parametrize("cell", FIXED_ORDER)
def test_fixed_order_deals_every_seed_the_same_sizes_in_the_same_order(cell):
    loaded = lib.load_cell(cell)
    mix, slots = loaded["mix"], loaded["model"]["run"]["n_slots"]
    vocab = loaded["model"]["vocab_size"]
    a = generate.client_requests(BIG, mix, vocab, slots)
    b = generate.client_requests(BIG + 1, mix, vocab, slots)
    assert a == generate.client_requests(BIG, mix, vocab, slots)

    def sizes(deal):
        return [[(len(r["tokens"]), r["max_new"]) for r in reqs]
                for reqs in deal]

    assert sizes(a) == sizes(b)
    # the seed draws every token id
    assert all(x["tokens"] != y["tokens"]
               for ra, rb in zip(a, b) for x, y in zip(ra, rb))
    # ONLY the clients that start in a slot are cut: every later
    # client's requests are whole ones of the mix's set, and with the
    # slots' uncut sizes they are the set
    assert len(a) == mix["clients"] == 3 * slots
    assert generate.cut_clients(mix, slots) == slots
    full = generate.request_sizes(mix)
    lo = mix["output_tokens"]["min"]
    whole = [s for reqs in sizes(a)[slots:] for s in reqs]
    later = [s for reqs in sizes(a)[:slots] for s in reqs[1:]]
    assert min(n for _, n in whole + later) >= lo
    firsts = [reqs[0] for reqs in sizes(a)[:slots]]
    rest = sorted(full)
    for s in whole + later:
        rest.remove(s)
    assert len(rest) == slots
    assert sorted(p for p, _ in rest) == sorted(p for p, _ in firsts)
    # the cut shares are spread evenly over (0, 1): one in each of
    # `slots` strata, so about half of the slots' tokens are left
    by_prompt = {}
    for p, n in rest:
        by_prompt.setdefault(p, []).append(n)
    shares = []
    for p, n in firsts:
        shares.append(min(n / m for m in by_prompt[p]))
    assert 0.4 < float(np.mean(shares)) < 0.6
    assert sum(n < lo for _, n in firsts) > slots // 4
    cut_tokens = sum(n for _, n in firsts)
    assert 0.35 < cut_tokens / sum(n for _, n in rest) < 0.65


def test_fixed_order_wants_the_slots_and_an_unknown_deal_is_refused():
    mix = lib.load_cell(FIXED_ORDER[0])["mix"]
    with pytest.raises(ValueError, match="n_slots"):
        generate.client_requests(BIG, mix, 100)
    with pytest.raises(ValueError, match="n_slots"):
        generate.client_requests(BIG, mix, 100, mix["clients"] + 1)
    with pytest.raises(ValueError, match="unknown deal"):
        generate.client_requests(BIG, dict(mix, deal="by_lot"), 100, 64)


def test_the_deals_census_counts_the_cut_requests():
    """serve.deal_census on a made-up run of 6 clients for 2 slots:
    the first admissions are the first two first tokens."""
    serve = lib.load_driver("serve")
    mix = {"deal": "fixed_order", "clients": 6}

    def record(client, k, first, n, end):
        return {"client": client, "k": k, "chunks": [[first, n]] if n else [],
                "tokens": [1] * n, "t_end": end}

    records = [
        record(0, 0, 1.0, 70, 5.0), record(2, 0, 1.1, 90, 6.0),
        record(1, 0, 1.2, 80, 12.0), record(3, 0, 6.5, 100, 13.0),
        record(0, 1, 7.0, 65, 14.0), record(4, 0, 0.0, 0, 20.0),
    ]
    window = {"ended": [r for r in records if 10.0 <= r["t_end"] <= 15.0]}
    census = serve.deal_census({"records": records}, window, mix, 2)
    assert census == {
        "first_admissions_cut": 1, "ended_in_window": 3, "ended_cut": 1,
        "ended_shortest_tokens": 65, "clients_started_s": None,
    }
    # a mix without the key cuts every client's first request
    census = serve.deal_census({"records": records}, window, {"clients": 6}, 2)
    assert (census["first_admissions_cut"], census["ended_cut"]) == (2, 2)


def test_warm_requests_and_sample():
    mix = lib.load_cell("mistral7b_serve_decode")["mix"]
    warm = generate.warm_requests(BIG, mix, 32768)
    assert [len(r["tokens"]) for r in warm] == [64, 128, 256, 512]
    picked = generate.sample_indices(BIG, 50, 6, always=17)
    assert len(set(picked)) == 6 and 17 in picked
    assert picked == generate.sample_indices(BIG, 50, 6, always=17)
    assert generate.sample_indices(BIG, 4, 6, always=2) == [0, 1, 2, 3]


def test_delivery_rate_does_not_step_with_the_windows_edges():
    """Bursts of 100 tokens every 0.5 s are 200 tokens/s wherever a
    10 s window falls; a pause between bursts counts in full; chunks
    that come without pauses take the plain count."""
    serve = lib.load_driver("serve")
    bursts = [{"chunks": [[0.5 * k + 0.001 * j, 25] for j in range(4)]}
              for k in range(60)]
    for open_at in (3.0, 3.2, 3.49, 3.501, 3.7):
        rate = serve.delivery_rate(bursts, open_at, open_at + 10.0)
        assert abs(rate - 200.0) < 0.5, (open_at, rate)
    stalled = [{"chunks": [[t + (2.0 if t > 8 else 0.0), n] for t, n in r["chunks"]]}
               for r in bursts]
    slow = serve.delivery_rate(stalled, 3.2, 13.2)
    assert 155.0 < slow < 170.0  # 16 periods and 2 s more for 16 bursts
    steady = [{"chunks": [[0.01 * k, 2] for k in range(3000)]}]
    assert abs(serve.delivery_rate(steady, 5.0, 15.0) - 200.0) < 1.0
