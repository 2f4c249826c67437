"""Whether what the timed path answered is correct.

Each answered request carries the estimate of its template over samples
``0 .. iterations-1`` of its seed. A sample of the requests drawn from the
run's seed (always with the request that ran the most iterations of each
template) is recomputed by the plain reference, once the window has closed
and the service has freed its device state. Numbers compared, each with the
limit the configuration states for it:

* ``estimate_rel_gap``: the widest ``|estimate - reference| / reference``;
* ``failed``: requests that never got an answer, or got an error;
* ``contract_broken``: answers that stopped before their precision target
  and their iteration cap, or that were computed past the cap (an answer
  from the results cache may have more samples than the cap asks for).
"""

from __future__ import annotations

import numpy as np

from bench.harness.reference import Reference


def failed(reqs) -> int:
    return sum(r.status not in ("done", "shed") for r in reqs)


def contract_broken(reqs) -> int:
    bad = 0
    for r in reqs:
        a = r.answer
        if r.status != "done" or a is None:
            continue
        n = int(a["iterations"])
        met = r.rel_stderr is not None and a["rel_stderr"] <= r.rel_stderr
        over = n > r.max_iters and not a.get("from_cache")
        if over or (n < r.max_iters and not met):
            bad += 1
    return bad


def sample(reqs, seed: int, size: int) -> list:
    """The requests to recompute: for each template the answered request
    with the most iterations, then others drawn from ``seed``."""
    done = [r for r in reqs if r.status == "done" and r.answer]
    picked = {}
    for r in done:
        best = picked.get(r.template)
        if best is None or r.answer["iterations"] > best.answer["iterations"]:
            picked[r.template] = r
    out = list(picked.values())
    rest = [r for r in done if r not in out]
    rng = np.random.default_rng([seed, 0xC4EC])
    extra = max(0, size - len(out))
    if rest and extra:
        out += [rest[i] for i in sorted(
            rng.choice(len(rest), min(extra, len(rest)), replace=False))]
    return out


def estimate_rel_gap(ref: Reference, templates: dict, picked) -> float:
    """Widest relative gap between an answer and the reference's estimate
    over the same samples. Counts are computed once per (template, seed),
    for as many samples as the longest answer needs."""
    need: dict[tuple, int] = {}
    for r in picked:
        key = (r.template, r.seed)
        need[key] = max(need.get(key, 0), int(r.answer["iterations"]))
    est = {}
    for (tpl, seed), n in need.items():
        t = templates[tpl]
        counts = ref.counts(t["edges"], t.get("root", 0), seed, n)
        est[(tpl, seed)] = (counts, ref.scale(t["edges"]))
    worst = 0.0
    for r in picked:
        counts, scale = est[(r.template, r.seed)]
        want = counts[: int(r.answer["iterations"])].mean() * scale
        worst = max(worst, float(abs(r.answer["estimate"] - want)
                                 / abs(want)))
    return worst


def compare(reqs, ref: Reference, templates: dict, seed: int, size: int,
            limits: dict) -> tuple[bool, dict]:
    """``(correct, {number: {"value", "limit"}})``."""
    picked = sample(reqs, seed, size)
    numbers = {
        "estimate_rel_gap": estimate_rel_gap(ref, templates, picked)
        if picked else float("inf"),
        "failed": failed(reqs),
        "contract_broken": contract_broken(reqs),
    }
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
