"""The load generator of the serving cells: a child process that never
imports jax, so that its Python shares no interpreter lock with the
engine's host loop. It reads the traffic file's parameters, makes the
requests from --seed (generate.py; --slots is the configuration's
run.n_slots, which a mix whose `deal` is "fixed_order" needs: it cuts
the first requests of clients 0 .. slots-1 only), and drives POST
/v1/generate with streamed replies from one thread per client. The
clients are started in index order. Under deal "fixed_order" the
order of the scheduler's queue IS the clients' order, by construction:
the gateway answers a request's status line once the request stands
in the queue (before any token), and client i + 1 is started when
client i has read that line; so clients 0 .. slots-1 start in a slot
and every run queues the others in one order. Any other mix's
clients are started one after the other without waiting, as ever.

Closed loop: each client sends its next request when the last one has
ended. The window opens at the wall-clock time --open-at (the clients
have been running since the process started, so the window opens on
a batch in steady state) and closes --seconds later; then every
client stops where it is. The result is one JSON object in --out.
"""

import argparse
import http.client
import json
import os
import sys
import threading
import time
from urllib.parse import urlparse

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import generate  # noqa: E402


class Client(threading.Thread):
    def __init__(self, index, addr, requests, close_at, timeout):
        super().__init__(name=f"client-{index}", daemon=True)
        self.index, self.requests = index, requests
        url = urlparse(addr)
        self.host, self.port = url.hostname, url.port
        self.close_at, self.timeout = close_at, timeout
        self.done = []      # one record per request that ended
        self.sent = 0       # requests handed to the server so far
        self.ran_out = False
        # set when the first request's status line is read (the
        # gateway writes it once the request is queued), or when the
        # client ended before that
        self.queued = threading.Event()

    def run(self):
        try:
            for k, request in enumerate(self.requests):
                if time.time() >= self.close_at:
                    return
                self.sent = k + 1
                record = self.one(k, request)
                self.done.append(record)
                if record["state"] == "open_at_close":
                    return  # the window closed under the request
            self.ran_out = True
        finally:
            self.queued.set()

    def one(self, k, request):
        body = json.dumps({
            "tokens": request["tokens"], "max_new": request["max_new"],
            "stream": True, "deadline_s": self.timeout,
        }).encode()
        record = {
            "client": self.index, "k": k,
            "prompt_tokens": len(request["tokens"]),
            "max_new": request["max_new"], "chunks": [], "tokens": [],
            "state": "unanswered", "t_send": time.time(),
        }
        conn = http.client.HTTPConnection(
            self.host, self.port, timeout=self.timeout)
        try:
            conn.request("POST", "/v1/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            self.queued.set()
            if resp.status != 200:
                record["state"] = f"http_{resp.status}"
                record["t_end"] = time.time()
                return record
            while True:
                if time.time() >= self.close_at:
                    record["state"] = "open_at_close"
                    break
                line = resp.readline()
                if not line:
                    break
                now = time.time()
                msg = json.loads(line)
                if "tokens" in msg:
                    record["chunks"].append([now, len(msg["tokens"])])
                    record["tokens"].extend(msg["tokens"])
                elif msg.get("done"):
                    record["state"] = msg.get("state", "done")
                elif "error" in msg:
                    record["state"] = "error:" + str(msg["error"])
            record["t_end"] = time.time()
            return record
        except (OSError, http.client.HTTPException) as e:
            record["state"] = f"exception:{type(e).__name__}"
            record["t_end"] = time.time()
            return record
        finally:
            conn.close()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--addr", required=True)
    ap.add_argument("--traffic", required=True, help="the mix, as JSON")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--open-at", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    mix = json.loads(args.traffic)
    if mix["loop"] != "closed":
        raise SystemExit(f"loop {mix['loop']!r}: only 'closed' is built")
    per_client = generate.client_requests(
        args.seed, mix, args.vocab, args.slots or None)
    close_at = args.open_at + args.seconds
    clients = [
        Client(i, args.addr, reqs, close_at, timeout=600.0)
        for i, reqs in enumerate(per_client)
    ]
    in_order = mix.get("deal") == "fixed_order"
    t_started = time.time()
    for c in clients:
        c.start()
        if in_order:
            c.queued.wait(timeout=30.0)
    t_all_started = time.time()
    for c in clients:
        c.join(timeout=max(0.0, close_at - time.time()) + 30.0)
    stuck = [c.index for c in clients if c.is_alive()]
    records = [r for c in clients for r in c.done]
    # how late the generator ran: in a closed loop, the time from one
    # reply's end to the next request's send, per client
    gaps = []
    for c in clients:
        for prev, nxt in zip(c.done, c.done[1:]):
            gaps.append((nxt["t_send"] - prev["t_end"]) * 1e3)
    out = {
        "open_at": args.open_at, "close_at": close_at,
        "started_at": t_started, "records": records,
        "clients_started_s": t_all_started - t_started,
        "clients_ran_out": [c.index for c in clients if c.ran_out],
        # the loop's margin: the fewest requests any client still had
        # to send when it stopped (0 with its last one sent)
        "least_requests_left": min(
            len(c.requests) - c.sent for c in clients),
        "clients_stuck": stuck,
        "send_gap_ms": {
            "n": len(gaps),
            "median": sorted(gaps)[len(gaps) // 2] if gaps else None,
            "max": max(gaps) if gaps else None,
        },
    }
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
