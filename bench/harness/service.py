"""The system under test: ``serve --http``'s service, started in-process.

The service is built by ``repro.launch.serve.start_http_service`` from the
same flags a user would pass to ``serve --http``; the benchmark only picks
them from the configuration file, and gives every run a fresh results cache
and ledger under a temporary directory.
"""

from __future__ import annotations

import gc
import os
import tempfile

from bench.harness.traffic import WARM_SEED, Client, Request


def serve_flags(cfg: dict, templates: list[str], workdir: str) -> list[str]:
    """``serve`` flags for a configuration: the named templates are
    advertised for the warm pool (edge-list ones with ``--template-edges``)."""
    flags = ["--http", "0", "--round-size", str(cfg["round_size"]),
             "--ledger", os.path.join(workdir, "ledger"),
             "--engine", cfg.get("engine", "pgbsc"),
             "--plan", cfg.get("plan", "optimized")]
    if cfg.get("results_cache", True):
        flags += ["--results-cache", os.path.join(workdir, "results.json")]
    if cfg.get("dtype"):
        flags += ["--dtype", cfg["dtype"]]
    names, edges = [], []
    for t in templates:
        spec = cfg["templates"][t]
        if isinstance(spec.get("send"), str):
            names.append(spec["send"])
        else:
            edges.append(",".join(f"{u}-{v}" for u, v in spec["edges"])
                         + f"@{spec.get('root', 0)}")
    flags += ["--templates", ",".join(names)]
    for e in edges:
        flags += ["--template-edges", e]
    return flags


class Service:
    """One running service and its HTTP front end on an ephemeral port."""

    def __init__(self, cfg: dict, graph, templates: list[str],
                 timeout_s: float, dtype: str | None = None):
        from repro.launch import serve

        self.workdir = tempfile.mkdtemp(prefix="bench_run_")
        c = dict(cfg, dtype=dtype or cfg.get("dtype"))
        args = serve.parse_args(serve_flags(c, templates, self.workdir))
        self.svc, self.httpd = serve.start_http_service(args, graph)
        self.port = self.httpd.server_address[1]
        self.client = Client(self.port, cfg["templates"], timeout_s)

    def warm(self, traffic: dict, phases: list | None = None) -> None:
        """One request per template of the traffic, for one round, at a seed
        the window never uses: every engine is built, and the one dispatch
        width the window uses (the round size: every cap is a multiple of
        it) is compiled and run once."""
        klass = traffic["classes"][0]["name"]
        for i, tpl in enumerate(traffic["templates"]):
            r = Request(idx=-1, template=tpl, seed=WARM_SEED + i,
                        klass=klass, rel_stderr=None,
                        max_iters=int(self.svc.round_size))
            conn = self.client.send(r)
            if conn is not None:
                conn.close()
            if r.status != "done":
                raise RuntimeError(f"warm-up request for {tpl} ended "
                                   f"{r.status}: {r.error}")
            if phases is not None:
                phases.append((f"warm {tpl}", r.t_done))

    def close(self) -> None:
        """Stop the front end and the dispatcher and free every engine's
        device arrays."""
        self.httpd.shutdown()
        self.httpd.server_close()
        self.svc.close()
        engines = list(self.svc.engine_cache._engines.values())
        engines += [g.engine for g in self.svc._groups.values()]
        for eng in engines:
            if not getattr(eng, "_released", True):
                eng.release()
        self.svc = self.httpd = None
        gc.collect()
