"""`requests_per_client` holds the loop closed for any program the
chip allows: the model of the loop (closed_loop.run_dry, over
generate.client_requests' own deal) runs no client out at 1.5 times
each cell's roofline rate, nor at today's; it DOES run one out where
the chip did (4 a client at 2900 tokens/s, PR 32's runs), which keeps
the model honest. And the load generator reports the margin it models.
Since PR 52 a mix whose `deal` is "fixed_order" deals every seed the
same sizes in the same order: the rule then reads ONE deal, and the
twelve seeds below are twelve readings of it."""

import functools
import http.server
import json
import os
import sys
import threading
import time

import pytest

import closed_loop
import lib

SEEDS = [2 ** 31 + 100 + s for s in range(12)]
MANIFEST = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
CELLS = {c["name"]: c for c in closed_loop.closed_loop_cells(MANIFEST)}
TODAY = {  # tokens/s (ledger, PR 31)
    "mistral7b_serve_decode": 1745.8,
    "mellum2_serve_context_decode": 1001.5,
}
ROOFLINE = {  # tokens/s, as the two mixes' `why` texts give it (PR 33)
    "mistral7b_serve_decode": 6001.0,
    "mellum2_serve_context_decode": 6181.0,
}


@functools.lru_cache(maxsize=None)
def deals(name, requests_per_client):
    mix = dict(CELLS[name]["mix"], requests_per_client=requests_per_client)
    slots = CELLS[name]["model"]["run"]["n_slots"]
    return [closed_loop.deal_outputs(seed, mix, slots) for seed in SEEDS]


def dry_runs(name, tokens_per_s, requests_per_client=None):
    cell = CELLS[name]
    per_client = requests_per_client or cell["mix"]["requests_per_client"]
    until = closed_loop.horizon_s(cell["mix"], MANIFEST["run_seconds"])
    return [
        closed_loop.run_dry(
            deal, cell["model"]["run"]["n_slots"], tokens_per_s, until)
        for deal in deals(name, per_client)
    ]


def test_every_serving_cell_is_a_closed_loop():
    serving = {w["name"] for w in MANIFEST["workloads"]
               if "serve" in lib.fill_cell(MANIFEST, dict(w))["model"]["driver"]}
    assert serving == set(CELLS) and set(TODAY) <= serving


# over every closed-loop cell the manifest has, so that a cell a later
# PR adds as files is held to the rule without an edit here
@pytest.mark.parametrize("name", sorted(CELLS))
def test_roofline_rate_is_what_the_traffic_file_says(name):
    roof = closed_loop.roofline_tokens_per_s(CELLS[name])
    assert f"{roof:.0f} tokens/s" in CELLS[name]["mix"]["why"]
    if name in ROOFLINE:
        assert roof == pytest.approx(ROOFLINE[name], rel=2e-3)
        assert roof > 3 * TODAY[name]  # the room the cell has to show a gain in


@pytest.mark.parametrize("name", sorted(CELLS))
def test_no_client_runs_out_at_one_and_a_half_times_the_roofline(name):
    roof = closed_loop.roofline_tokens_per_s(CELLS[name])
    runs = dry_runs(name, closed_loop.HEADROOM * roof)
    assert [out for out, _ in runs] == [[]] * len(SEEDS)
    assert min(left for _, left in runs) >= 1


FIXED_ORDER = sorted(
    name for name, c in CELLS.items() if c["mix"].get("deal") == "fixed_order")


def test_the_two_unsteady_cells_took_the_fixed_order():
    assert FIXED_ORDER == [
        "gigachat3_serve_latent_decode", "mellum2_serve_context_decode"]


@pytest.mark.parametrize("name", FIXED_ORDER)
def test_a_fixed_order_is_one_deal_and_the_rule_reads_it(name):
    """Every seed's deal of output sizes is the same one, the rule's
    `deals` holds it once, and at 1.5 times the roofline it leaves
    every client two requests or more; four a client fewer run
    clients out; the mix's `why` carries the numbers."""
    cell = CELLS[name]
    mix, slots = cell["mix"], cell["model"]["run"]["n_slots"]
    dealt = deals(name, mix["requests_per_client"])
    assert all(d == dealt[0] for d in dealt)
    assert closed_loop.deals(mix, slots, 24) == [dealt[0]]
    # only the slots' clients are cut: the others' first outputs are whole
    lo = mix["output_tokens"]["min"]
    assert min(d[0] for d in dealt[0][slots:]) >= lo
    assert min(d[0] for d in dealt[0][:slots]) < lo // 8
    roof = closed_loop.roofline_tokens_per_s(cell)
    until = closed_loop.horizon_s(mix, MANIFEST["run_seconds"])
    out, left = closed_loop.run_dry(
        dealt[0], slots, closed_loop.HEADROOM * roof, until)
    assert out == [] and left >= 2
    assert f"least_requests_left {left}" in mix["why"]
    fewer = dict(mix, requests_per_client=mix["requests_per_client"] - 4)
    out, _ = closed_loop.run_dry(
        closed_loop.deals(fewer, slots, 1)[0], slots,
        closed_loop.HEADROOM * roof, until)
    assert out
    assert f"{sum(d[0] for d in dealt[0][:slots]) / 1e3:.1f} k tokens" \
        in mix["why"]


def test_a_mix_without_the_key_is_read_over_as_many_deals_as_asked():
    cell = CELLS["mistral7b_serve_decode"]
    dealt = closed_loop.deals(
        cell["mix"], cell["model"]["run"]["n_slots"], 3)
    assert len(dealt) == 3 and dealt[0] != dealt[1] != dealt[2]


@pytest.mark.parametrize("name", sorted(TODAY))
def test_no_client_runs_out_at_todays_rate_and_eight_are_left(name):
    runs = dry_runs(name, TODAY[name])
    assert [out for out, _ in runs] == [[]] * len(SEEDS)
    assert min(left for _, left in runs) >= 8


def test_four_a_client_run_out_where_the_chip_showed_it():
    runs = dry_runs("mistral7b_serve_decode", 2900.0, requests_per_client=4)
    assert all(out for out, _ in runs)
    assert {left for _, left in runs} == {0}
    # and not at the rate of that day, where the chip never failed
    assert not any(out for out, _ in dry_runs(
        "mistral7b_serve_decode", TODAY["mistral7b_serve_decode"], 4))


def test_twelve_is_the_least_multiple_of_four_that_holds():
    for name in TODAY:
        rate = closed_loop.HEADROOM * closed_loop.roofline_tokens_per_s(CELLS[name])
        assert all(out for out, _ in dry_runs(name, rate, 8)), name
        assert CELLS[name]["mix"]["requests_per_client"] == 12


class _Server(http.server.BaseHTTPRequestHandler):
    """Answers POST /v1/generate as the gateway streams: the tokens in
    one chunk, then the closing line."""

    arrivals = []  # (prompt tokens, max_new) in the order they came

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.arrivals.append((len(body["tokens"]), body["max_new"]))
        self.send_response(200)
        self.end_headers()
        threading.Event().wait(0.05 * body["max_new"])
        self.wfile.write(
            json.dumps({"tokens": [1] * body["max_new"]}).encode() + b"\n"
            + json.dumps({"done": True, "state": "done"}).encode() + b"\n")

    def log_message(self, *args):
        pass


def run_loadgen(tmp_path, monkeypatch, mix, seconds, slots=0):
    loadgen = lib.load_module(
        os.path.join(lib.BENCH, "drivers", "loadgen.py"), "perfbench_loadgen")
    _Server.arrivals.clear()
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Server)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    out = os.path.join(tmp_path, "load.json")
    try:
        monkeypatch.setattr(sys, "argv", [
            "loadgen.py", "--addr", f"http://127.0.0.1:{server.server_port}",
            "--traffic", json.dumps(mix), "--seed", str(SEEDS[0]),
            "--vocab", "100", "--slots", str(slots),
            "--open-at", repr(time.time()),
            "--seconds", str(seconds), "--out", out,
        ])
        assert loadgen.main() == 0
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)
    return lib.read_json(out)


def small_mix(requests_per_client):
    return dict(
        CELLS["mistral7b_serve_decode"]["mix"], clients=3,
        requests_per_client=requests_per_client,
        prompt_tokens={"min": 4, "max": 8, "distribution": "log_uniform"},
        output_tokens={"min": 2, "max": 4, "distribution": "log_uniform"},
    )


def test_loadgen_reports_the_least_requests_left(tmp_path, monkeypatch):
    """Requests of 0.1-0.2 s: in a window of 1 s no client gets
    through 40, and the one that sent most sets the margin."""
    load = run_loadgen(tmp_path, monkeypatch, small_mix(40), seconds=1.0)
    assert load["clients_ran_out"] == [] and load["clients_stuck"] == []
    sent = {}
    for r in load["records"]:
        sent[r["client"]] = max(sent.get(r["client"], 0), r["k"] + 1)
    assert len(sent) == 3 and 3 <= max(sent.values()) <= 12
    # a request the window closed under was sent, and has its record
    assert load["least_requests_left"] == 40 - max(sent.values())


def test_loadgen_reports_nought_left_where_a_client_ran_out(tmp_path, monkeypatch):
    load = run_loadgen(tmp_path, monkeypatch, small_mix(2), seconds=2.0)
    assert sorted(load["clients_ran_out"]) == [0, 1, 2]
    assert load["least_requests_left"] == 0


def test_a_fixed_order_queues_the_clients_in_their_order(tmp_path, monkeypatch):
    """Under deal fixed_order client i + 1 is started when client i
    has read its status line, which the server writes once it has the
    request: the first requests arrive in the clients' order, the
    slots' cut ones first, whatever the threads' luck."""
    import generate

    mix = dict(small_mix(40), clients=24, deal="fixed_order")
    load = run_loadgen(tmp_path, monkeypatch, mix, seconds=0.5, slots=8)
    dealt = generate.client_requests(SEEDS[0], mix, 100, 8)
    firsts = [(len(reqs[0]["tokens"]), reqs[0]["max_new"]) for reqs in dealt]
    assert _Server.arrivals[:24] == firsts
    assert load["clients_started_s"] < 5.0 and load["clients_stuck"] == []

