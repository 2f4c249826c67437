"""The one traffic generator: closed and open loops from a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only; this
module turns them and ``--seed`` into requests and drives them through
``POST /count``. Every seed gets the same work in another order: in each
stretch of constant rate, the share of each template, precision class,
shared-seed flag and tenant is allocated as exact counts and shuffled, and
the arrivals use one fixed set of exponential gaps, shuffled. Fresh request
seeds come from ``--seed``.

``closed``: ``clients`` threads each send a request and wait for its answer
before sending the next, until ``--seconds`` have passed; the window then
runs on until the requests in flight are answered.

``open``: requests are sent at scheduled times (Poisson-like at
``rate_per_s``, with ``burst.factor`` times that rate for ``burst.length_s``
every ``burst.every_s``; each stretch holds its expected count exactly)
whatever the answers do; latency counts from the scheduled time, and the
generator's lateness is recorded per request.

Both loops wait at most ``ANSWER_GRACE_S`` past the close for answers. Every
request of the window that has none by then, sent or still queued, is
``unanswered``: it counts as failed and as infinitely late, and an answer
that comes after that changes nothing.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import math
import queue
import threading
import time

import numpy as np

FRESH_SEED_HI = 1 << 30        # fresh request seeds are in [1, 2**30)
WARM_SEED = 1 << 30            # warm-up requests use seeds from here up
ANSWER_GRACE_S = 60.0          # an answer may come this long after close


@dataclasses.dataclass
class Request:
    idx: int
    template: str
    seed: int
    klass: str
    rel_stderr: float | None
    max_iters: int
    tenant: str = "default"
    t_sched: float | None = None      # offset from the window's start (open)
    # filled in when it is driven
    t_send: float | None = None
    t_done: float | None = None
    http: int | None = None
    status: str = "pending"
    answer: dict | None = None
    error: str | None = None


def zipf_shares(n: int, s: float) -> list[float]:
    w = [1.0 / (i + 1) ** s for i in range(n)]
    tot = sum(w)
    return [x / tot for x in w]


def exact_counts(total: int, shares) -> list[int]:
    """Largest-remainder allocation of ``total`` items to ``shares``."""
    raw = [total * s for s in shares]
    out = [int(math.floor(r)) for r in raw]
    rest = sorted(range(len(raw)), key=lambda i: out[i] - raw[i])
    for i in rest[: total - sum(out)]:
        out[i] += 1
    return out


def fresh_seeds(rng: np.random.Generator, n: int) -> list[int]:
    """``n`` distinct request seeds in ``[1, FRESH_SEED_HI)``."""
    out: list[int] = []
    seen: set[int] = set()
    while len(out) < n:
        for x in rng.integers(1, FRESH_SEED_HI, size=n - len(out)).tolist():
            if x not in seen:
                seen.add(x)
                out.append(x)
    return out


def _kinds(p: dict) -> list[tuple]:
    """(template, shared?, class) for each template x seed kind x class,
    with its share, in a fixed order."""
    tpls = p["templates"]
    t_sh = zipf_shares(len(tpls), p.get("template_zipf_s", 0.0))
    shared = float(p.get("shared_seed_share", 0.0))
    out = []
    for t, ts in zip(tpls, t_sh):
        for is_shared, ss in ((True, shared), (False, 1.0 - shared)):
            for c in p["classes"]:
                share = ts * ss * float(c["share"])
                if share > 0:
                    out.append(((t, is_shared, c["name"]), share))
    return out


def _class(p: dict, name: str) -> dict:
    return next(c for c in p["classes"] if c["name"] == name)


def plan_open(p: dict, seed: int, seconds: float) -> list[Request]:
    """Every request of an open-loop window of ``seconds``, in send order.
    Each stretch of constant rate holds its exact share of every kind of
    request and of every tenant; only the order within it, and the fresh
    seeds, change with ``seed``."""
    rng = np.random.default_rng([seed, 0x0BE7])
    kinds = _kinds(p)
    tenants = [f"t{i:02d}" for i in range(int(p.get("tenants", 1)))]
    t_shares = zipf_shares(len(tenants), p.get("tenant_zipf_s", 0.0))

    def spread(names, shares, c):
        out = [x for x, k in zip(names, exact_counts(c, shares))
               for _ in range(k)]
        rng.shuffle(out)
        return out

    plan = []
    for times in arrivals(rng, float(p["rate_per_s"]), p.get("burst"),
                          seconds):
        c = len(times)
        plan += zip(times, spread([k for k, _ in kinds],
                                  [s for _, s in kinds], c),
                    spread(tenants, t_shares, c))
    n_fresh = sum(1 for _, (_, shared, _), _ in plan if not shared)
    seeds = iter(fresh_seeds(rng, n_fresh))
    out = []
    for i, (t, (tpl, shared, klass), tenant) in enumerate(plan):
        c = _class(p, klass)
        out.append(Request(
            idx=i, template=tpl, seed=0 if shared else next(seeds),
            klass=klass, rel_stderr=c.get("rel_stderr"),
            max_iters=int(c["max_iters"]), tenant=tenant,
            t_sched=float(t)))
    return out


def segments(rate: float, burst, seconds: float) -> list[tuple]:
    """``(start, end, expected arrivals)`` of each stretch of constant rate:
    ``burst["factor"]`` x ``rate`` for ``burst["length_s"]`` at the start of
    every ``burst["every_s"]``, ``rate`` otherwise."""
    if not burst:
        return [(0.0, seconds, rate * seconds)]
    every, length, f = burst["every_s"], burst["length_s"], burst["factor"]
    out, t = [], 0.0
    while t < seconds:
        for s0, s1, r in ((t, t + length, rate * f), (t + length, t + every,
                                                      rate)):
            s1 = min(s1, seconds)
            if s1 > s0:
                out.append((s0, s1, r * (s1 - s0)))
        t += every
    return out


def arrivals(rng: np.random.Generator, rate: float, burst,
             seconds: float) -> list[np.ndarray]:
    """Arrival times per stretch: each stretch gets its expected count
    (largest remainder over the window's total), spaced by one fixed set of
    exponential quantiles, shuffled."""
    segs = segments(rate, burst, seconds)
    total = int(round(sum(lam for _, _, lam in segs)))
    counts = exact_counts(total, [lam / max(total, 1e-9) for _, _, lam in segs])
    out = []
    for (s0, s1, _), c in zip(segs, counts):
        q = (np.arange(c + 1) + 0.5) / (c + 1)
        gaps = -np.log1p(-q)
        rng.shuffle(gaps)
        out.append(s0 + np.cumsum(gaps)[:c] / gaps.sum() * (s1 - s0))
    return out


def closed_stream(p: dict, seed: int):
    """Endless request stream of a closed loop (fresh seeds unless the
    traffic shares them)."""
    rng = np.random.default_rng([seed, 0xC105])
    kinds = _kinds(p)
    shares = np.asarray([s for _, s in kinds])
    used: set[int] = set()
    i = 0
    while True:
        tpl, shared, klass = kinds[rng.choice(len(kinds), p=shares
                                              / shares.sum())][0]
        s = 0
        if not shared:
            s = int(rng.integers(1, FRESH_SEED_HI))
            while s in used:
                s = int(rng.integers(1, FRESH_SEED_HI))
            used.add(s)
        c = _class(p, klass)
        yield Request(idx=i, template=tpl, seed=s, klass=klass,
                      rel_stderr=c.get("rel_stderr"),
                      max_iters=int(c["max_iters"]))
        i += 1


# ------------------------------------------------------------------ client
class Client:
    """``POST /count`` over keep-alive connections, one per sending thread.

    Connections are opened before the window, one at a time: the service's
    HTTP server keeps a listen backlog of 5, and a burst of connects would
    overflow it and be reset."""

    def __init__(self, port: int, templates: dict, timeout_s: float = 300.0):
        self.port = port
        self.templates = templates
        self.timeout_s = timeout_s
        self.lock = threading.Lock()    # guards every request's outcome

    def connect(self) -> http.client.HTTPConnection:
        c = http.client.HTTPConnection("127.0.0.1", self.port,
                                       timeout=self.timeout_s + 30)
        c.connect()
        return c

    def body(self, r: Request) -> dict:
        t = self.templates[r.template]
        tpl = t["send"] if "send" in t else {"edges": t["edges"],
                                             "root": t.get("root", 0),
                                             "name": r.template}
        return {"graph": "g", "templates": [tpl], "seed": r.seed,
                "rel_stderr": r.rel_stderr, "max_iters": r.max_iters,
                "qos": {"class": r.klass, "tenant": r.tenant},
                "wait": True, "timeout_s": self.timeout_s}

    def send(self, r: Request, conn: http.client.HTTPConnection | None = None
             ) -> http.client.HTTPConnection | None:
        """Send ``r`` on ``conn`` (a new connection if None), wait for its
        answer and fill in its outcome; returns the connection to reuse, or
        None after a transport error."""
        payload = json.dumps(self.body(r)).encode()
        r.t_send = time.perf_counter()
        try:
            conn = conn or self.connect()
            conn.request("POST", "/count", body=payload,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = json.loads(resp.read() or b"{}")
        except (OSError, http.client.HTTPException, ValueError) as exc:
            self._settle(r, "error", error=f"{type(exc).__name__}: {exc}")
            if conn is not None:
                conn.close()
            return None
        ent = (data.get("requests") or [{}])[0]
        self._settle(r, ent.get("status", "error"), http=resp.status,
                     answer=ent.get("result"),
                     error=ent.get("error") or data.get("error"))
        return conn

    def _settle(self, r: Request, status: str, **outcome) -> None:
        """Record ``r``'s outcome, unless the window has given it up."""
        t = time.perf_counter()
        with self.lock:
            if r.status != "pending":
                return
            r.t_done, r.status = t, status
            for k, v in outcome.items():
                setattr(r, k, v)

    def give_up(self, reqs) -> None:
        """Mark every request of ``reqs`` still without an outcome as
        ``unanswered``, for good."""
        with self.lock:
            for r in reqs:
                if r.status == "pending":
                    r.status = "unanswered"


# ------------------------------------------------------------------- loops
@dataclasses.dataclass
class Window:
    requests: list[Request]
    t0: float                 # perf_counter at the window's start
    t_close: float            # sending stopped
    t_end: float              # last answer in (or the grace ran out)


def run_closed(client: Client, p: dict, seed: int, seconds: float,
               trace=None, grace_s: float = ANSWER_GRACE_S) -> Window:
    """``trace``, if given, is opened before the first send and closed once
    the last request in flight is answered: the whole window."""
    stream = closed_stream(p, seed)
    lock = threading.Lock()
    sent: list[Request] = []

    def worker(conn):
        while time.perf_counter() < close:
            with lock:
                r = next(stream)
                sent.append(r)
            r.t_sched = time.perf_counter() - t0
            conn = client.send(r, conn)
        if conn is not None:
            conn.close()

    conns = [client.connect() for _ in range(int(p["clients"]))]
    threads = [threading.Thread(target=worker, args=(c,), daemon=True,
                                name=f"bench-client-{i}")
               for i, c in enumerate(conns)]
    if trace is not None:
        trace.start()
    t0 = time.perf_counter()
    close = t0 + seconds
    for t in threads:
        t.start()
    for t in threads:
        t.join(max(0.0, close + grace_s - time.perf_counter()))
    with lock:
        reqs = sorted(sent, key=lambda r: r.idx)
    t_end = _give_up(client, reqs, close)
    if trace is not None:
        trace.stop()
    return Window(reqs, t0, close, t_end)


def run_open(client: Client, p: dict, seed: int, seconds: float,
             trace=None, grace_s: float = ANSWER_GRACE_S) -> Window:
    """Send every planned request at its time through ``senders`` threads.
    ``trace``, if given, covers the offsets ``p["trace"]["from_s"]`` to
    ``p["trace"]["to_s"]`` into the window (opened and closed by this
    scheduling thread)."""
    reqs = plan_open(p, seed, seconds)
    marks = []
    if trace is not None:
        tw = p["trace"]
        marks = [(min(tw["from_s"], seconds), trace.start),
                 (min(tw["to_s"], seconds), trace.stop)]
    work: queue.Queue = queue.Queue()

    def sender(conn):
        while True:
            r = work.get()
            if r is None:
                if conn is not None:
                    conn.close()
                return
            if r.status == "pending":
                conn = client.send(r, conn)
            work.task_done()

    conns = [client.connect() for _ in range(int(p.get("senders", 64)))]
    senders = [threading.Thread(target=sender, args=(c,), daemon=True,
                                name=f"bench-sender-{i}")
               for i, c in enumerate(conns)]
    for t in senders:
        t.start()
    t0 = time.perf_counter()
    for r in reqs:
        due = t0 + r.t_sched
        while marks and t0 + marks[0][0] <= due:
            off, fn = marks.pop(0)
            _sleep_until(t0 + off)
            fn()
        _sleep_until(due)
        work.put(r)
    close = t0 + seconds
    for off, fn in marks:
        _sleep_until(t0 + off)
        fn()
    deadline = close + grace_s
    while work.unfinished_tasks and time.perf_counter() < deadline:
        time.sleep(0.05)
    t_end = _give_up(client, reqs, close)
    for _ in senders:
        work.put(None)
    return Window(reqs, t0, close, t_end)


def _give_up(client: Client, reqs, close: float) -> float:
    """Give up the requests still without an answer; the window's end: the
    last answer, or now if one was given up."""
    client.give_up(reqs)
    if any(r.status == "unanswered" for r in reqs):
        return time.perf_counter()
    return max([r.t_done for r in reqs if r.t_done] + [close])


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))
