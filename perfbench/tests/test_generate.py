"""The generator repeats exactly for a seed, differs across seeds,
and gives every seed the same amount of work."""

import numpy as np

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
