"""Seeded traffic: the same seed gives the same requests; another seed the
same work in another order. A service that stops answering fails the
requests it leaves, in both loops."""

import collections
import http.server
import json
import threading
import time
import types

import pytest

from bench.harness import check, traffic
from bench.harness.cell import BENCH_DIR
from bench.harness.readers import INF, latencies, percentile

CLOSED = json.loads((BENCH_DIR / "traffic" / "u7-closed.json").read_text())
# an open loop: no cell sends one yet, so its parameters live here
OPEN = {
    "loop": "open", "rate_per_s": 48.0,
    "burst": {"every_s": 10.0, "length_s": 1.0, "factor": 4.0},
    "templates": ["u3", "path4", "star4", "u5", "u7", "tree6"],
    "template_zipf_s": 1.0, "tenants": 16, "tenant_zipf_s": 1.0,
    "shared_seed_share": 0.7,
    "classes": [
        {"name": "interactive", "share": 0.6, "rel_stderr": 0.1,
         "max_iters": 32},
        {"name": "batch", "share": 0.4, "rel_stderr": 0.05,
         "max_iters": 64}],
    "senders": 8, "timeout_s": 30,
}


def key(r):
    return (r.template, r.seed, r.klass, r.tenant, r.t_sched)


def test_open_plan_is_seeded():
    a = traffic.plan_open(OPEN, 7, 30.0)
    b = traffic.plan_open(OPEN, 7, 30.0)
    c = traffic.plan_open(OPEN, 2 ** 31 + 5, 30.0)
    assert [key(r) for r in a] == [key(r) for r in b]
    assert [key(r) for r in a] != [key(r) for r in c]


def test_open_plan_same_work_every_seed():
    def work(reqs):
        return collections.Counter((r.template, r.seed == 0, r.klass)
                                   for r in reqs)

    plans = [traffic.plan_open(OPEN, s, 30.0) for s in (1, 2, 3)]
    assert work(plans[0]) == work(plans[1]) == work(plans[2])
    b = OPEN["burst"]
    for k in range(3):     # each burst holds the same mix too
        lo, hi = k * b["every_s"], k * b["every_s"] + b["length_s"]
        bursts = [work(r for r in p if lo <= r.t_sched < hi) for p in plans]
        assert bursts[0] == bursts[1] == bursts[2]
    gaps = [sorted(round(b.t_sched - a.t_sched, 9) for a, b in zip(p, p[1:]))
            for p in plans]
    assert len({len(g) for g in gaps}) == 1
    n = len(plans[0])
    b = OPEN["burst"]
    per = b["length_s"] * b["factor"] + b["every_s"] - b["length_s"]
    assert n == round(OPEN["rate_per_s"] * per * 3)
    shared = sum(r.seed == 0 for r in plans[0]) / n
    assert shared == pytest.approx(OPEN["shared_seed_share"], abs=0.02)
    fresh = [r.seed for r in plans[0] if r.seed]
    assert len(fresh) == len(set(fresh))
    assert all(0 <= r.t_sched < 30.0 for r in plans[0])


def test_bursts_hold_their_expected_count():
    rate, b = 20.0, OPEN["burst"]
    for seed in (5, 6):
        reqs = traffic.plan_open(dict(OPEN, rate_per_s=rate), seed, 40.0)
        for k in range(4):
            t0 = k * b["every_s"]
            n_burst = sum(t0 <= r.t_sched < t0 + b["length_s"] for r in reqs)
            assert n_burst == rate * b["factor"] * b["length_s"]
        assert len(reqs) == rate * 4 * (b["factor"] + 9)


def test_segments_cut_at_the_window():
    segs = traffic.segments(10.0, OPEN["burst"], 15.0)
    assert segs == [(0.0, 1.0, 40.0), (1.0, 10.0, 90.0),
                    (10.0, 11.0, 40.0), (11.0, 15.0, 40.0)]


def test_closed_stream_is_seeded_and_fresh():
    a = [next(s) for s in [traffic.closed_stream(CLOSED, 9)] for _ in range(1)]
    s1, s2 = traffic.closed_stream(CLOSED, 9), traffic.closed_stream(CLOSED, 9)
    x = [next(s1) for _ in range(50)]
    y = [next(s2) for _ in range(50)]
    assert [(r.template, r.seed) for r in x] == [(r.template, r.seed)
                                                for r in y]
    assert len({r.seed for r in x}) == 50 and all(r.seed for r in x)
    assert a[0].seed == x[0].seed


class StallingService:
    """``POST /count`` that answers its first ``answers`` requests at once
    and holds every later one until released."""

    def __init__(self, answers: int):
        self.answers, self.seen = answers, 0
        self.lock, self.release = threading.Lock(), threading.Event()
        svc = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                body = json.loads(self.rfile.read(
                    int(self.headers["Content-Length"])))
                with svc.lock:
                    svc.seen += 1
                    stall = svc.seen > svc.answers
                if stall:
                    svc.release.wait(30)
                out = json.dumps({"requests": [{"status": "done", "result": {
                    "estimate": 1.0, "iterations": body["max_iters"],
                    "rel_stderr": 0.0}}]}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(out)))
                self.end_headers()
                self.wfile.write(out)

        self.httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                                      Handler)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()
        templates = {t: {"send": t} for t in OPEN["templates"]}
        self.client = traffic.Client(self.httpd.server_address[1], templates,
                                     timeout_s=30)

    def stop(self):
        self.release.set()
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture
def stalling():
    made = []

    def make(answers):
        made.append(StallingService(answers))
        return made[-1]

    yield make
    for s in made:
        s.stop()


def test_open_loop_counts_what_a_stalled_service_leaves(stalling):
    svc = stalling(answers=10)
    p = dict(OPEN, rate_per_s=20.0, burst=None)
    w = traffic.run_open(svc.client, p, 3, 1.0, grace_s=0.3)
    planned = traffic.plan_open(p, 3, 1.0)
    assert len(w.requests) == len(planned) == 20
    done = [r for r in w.requests if r.status == "done"]
    assert len(done) == 10
    assert check.failed(w.requests) == 10
    assert all(r.status == "unanswered" for r in w.requests
               if r.status != "done")
    lat = latencies(types.SimpleNamespace(window=w))
    assert len(lat) == 20 and lat.count(INF) == 10
    assert percentile(lat, 95) == INF
    svc.release.set()          # a late answer changes nothing
    time.sleep(0.3)
    assert check.failed(w.requests) == 10


def test_closed_loop_counts_the_request_in_flight(stalling):
    svc = stalling(answers=3)
    w = traffic.run_closed(svc.client, CLOSED, 5, 0.5, grace_s=0.3)
    assert [r.status for r in w.requests] == ["done"] * 3 + ["unanswered"]
    assert check.failed(w.requests) == 1
    assert w.t_end >= w.t_close + 0.3
