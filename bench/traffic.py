"""The one traffic generator: a mix file's ``kind`` picks the client loop,
and its numbers set the loop up.

* ``refactor`` — one closed-loop client that keeps the configuration's
  pattern and sends new coefficients and a new right-hand side with every
  request, through ``SolverEngine.solve``: a new numeric factorization, the
  device sweeps and the refinement, on a cached plan.
* ``new_pattern`` — one closed-loop client that sends ``plan`` requests
  over the RPC front end, each for a pattern not seen before (the grid with
  a seeded share of its edges removed), so every request misses the plan
  cache.

Every request's inputs are made in set-up from ``--seed``; nothing is made
inside the window. A request that raises counts as failed.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import numpy as np

from bench import deploy, reference, work

__all__ = ["Window", "make_loop", "RefactorLoop", "NewPatternLoop"]

#: the limits ``correct`` is decided by, one file for every cell
LIMITS = json.loads((Path(__file__).parent / "limits.json").read_text())


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def _log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


@dataclasses.dataclass
class Window:
    records: list
    seconds: float
    max_late_s: float
    mean_late_s: float


def _check(name: str, value, limit, ok: bool) -> dict:
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


class _Loop:
    """What every loop shares: the closed-loop window and its records."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.limits = LIMITS[traffic["kind"]]

    @staticmethod
    def peaks() -> dict:
        import jax

        kind = jax.devices()[0].device_kind
        table = json.loads((Path(__file__).parent / "peaks.json").read_text())
        if kind not in table:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           f"bench/peaks.json")
        return table[kind]

    def _window(self, seconds: float, send, limit: int) -> Window:
        """Closed loop: send request i, wait for its answer, send i + 1,
        until ``seconds`` have passed or ``limit`` requests were sent; the
        request open at the deadline is waited for and counted, so the
        window holds whole requests."""
        from jax.profiler import TraceAnnotation

        records = []
        late = []
        pc = time.perf_counter
        with TraceAnnotation("bench.window"):
            t_begin = pc()
            t_prev = t_begin
            i = 0
            while pc() - t_begin < seconds and i < limit:
                t0 = pc()
                late.append(t0 - t_prev)
                with TraceAnnotation("bench.request"):
                    try:
                        rec = send(i)
                        rec["ok"] = True
                    except Exception as exc:  # counted as failed
                        rec = {"ok": False, "error": repr(exc), "spans": {}}
                t_prev = pc()
                rec.update(i=i, t0=t0 - t_begin, t1=t_prev - t_begin)
                records.append(rec)
                i += 1
            t_end = pc()
        return Window(records, t_end - t_begin, max(late, default=0.0),
                      float(np.mean(late)) if late else 0.0)

    def close(self) -> None:
        pass

    def kernel_work(self, records, peaks) -> dict:
        return {}


class RefactorLoop(_Loop):
    """New coefficients on the configuration's pattern, one solve each."""

    def setup(self, engine) -> dict:
        from repro.sparse.csr import CSRMatrix

        pc = time.perf_counter
        t = self.traffic
        self.engine = engine
        t0 = pc()
        n, u, v = deploy.stencil_edges(self.config)
        self.pattern = pat = deploy.pattern(n, u, v)
        sets = int(t["value_sets"])
        k = int(t["rhs"])

        def make(rng, count):
            mats, rhs = [], []
            for j in range(count):
                data = deploy.values(pat, rng, t["weight"], t["shift"])
                mats.append(CSRMatrix(pat.indptr, pat.indices, data, (n, n),
                                      f"{self.config['name']}-{j}"))
                b = rng.standard_normal((n, k) if k > 1 else n)
                rhs.append(b)
            return mats, rhs

        self.mats, self.rhs = make(_rng(self.seed, 0), sets)
        warm_mats, warm_rhs = make(_rng(self.seed, 1),
                                   int(t["warmup_requests"]))
        t_inputs = pc() - t0
        t0 = pc()
        self.plan = engine.plan(self.mats[0])
        t_plan = pc() - t0
        t0 = pc()
        for a, b in zip(warm_mats, warm_rhs):
            engine.solve(a, b)
        t_warm = pc() - t0
        _log(f"pattern n={n} nnz={pat.nnz}, ordering {self.plan.algorithm}, "
             f"nnz(L)={self.plan.nnz_L}, flops={self.plan.predicted_flops}")
        return {"inputs": t_inputs, "plan": t_plan, "warm-up": t_warm}

    def _send(self, i: int) -> dict:
        from repro.core.reqctx import RequestContext

        j = i % len(self.mats)
        ctx = RequestContext.mint()
        r = self.engine.solve(self.mats[j], self.rhs[j], ctx=ctx)
        return {"j": j, "x": r["x"], "spans": dict(ctx.spans),
                "algorithm": r["algorithm"],
                "iterations": r["refine_iterations"],
                "path": (r["backend"], r["sweep"], r["solve_dtype"])}

    def run(self, seconds: float) -> Window:
        return self._window(seconds, self._send, 1 << 62)

    def check(self, records) -> list:
        """Every solve's fp64 residual, a seeded sample's distance to
        SuperLU's fp64 solution, the served path and ordering."""
        done = [r for r in records if r["ok"]]
        lim = self.limits
        out = []
        if not done:
            return out
        res = []
        for r in done:
            a = self.mats[r["j"]]
            res.append(reference.relative_residual(
                reference.matrix(a.indptr, a.indices, a.data), r["x"],
                self.rhs[r["j"]]))
        out.append(_check("residual_max", max(res), lim["residual_max"],
                          max(res) <= lim["residual_max"]))
        rng = _rng(self.seed, 2)
        pick = sorted(rng.choice(len(done), min(len(done),
                                                 int(lim["sample"])),
                                 replace=False).tolist())
        errs = []
        for i in pick:
            r = done[i]
            a = self.mats[r["j"]]
            ref = reference.solve(reference.matrix(a.indptr, a.indices,
                                                   a.data), self.rhs[r["j"]])
            errs.append(reference.relative_error(r["x"], ref))
        out.append(_check("forward_error_max", max(errs),
                          lim["forward_error_max"],
                          max(errs) <= lim["forward_error_max"]))
        want = tuple(self.config["engine"][k]
                     for k in ("backend", "sweep", "solve_dtype"))
        off_path = sum(1 for r in done if tuple(r["path"]) != want)
        out.append(_check("off_path", off_path, 0, off_path == 0))
        host, _ = self.engine.selector.select_batch([self.mats[0]],
                                                    path="host")
        mismatch = sum(1 for r in done if r["algorithm"] != host[0])
        out.append(_check("ordering_mismatch", mismatch, 0, mismatch == 0))
        return out

    def kernel_work(self, records, peaks) -> dict:
        """(flops, bytes, least seconds) of each kernel over the window's
        completed solves: one factorization per solve, and one forward and
        one backward sweep per pass (the first solve plus one per
        refinement iteration), at the true RHS count."""
        from repro.sparse.schedule import build_schedule

        done = [r for r in records if r["ok"]]
        fronts = work.fronts_of(build_schedule(self.plan.sym),
                                self.plan.sym.counts)
        k = int(self.traffic["rhs"])
        fac = work.total([work.factor_front(c, m) for c, m, _ in fronts],
                         peaks)
        sweep = work.total([work.sweep_front(p, k) for _, _, p in fronts],
                           peaks)
        passes = sum(2 * (1 + (r["iterations"] or 0)) for r in done)
        n = len(done)
        return {"frontal_factor": tuple(n * x for x in fac),
                "tri_solve": tuple(passes * x for x in sweep)}


class NewPatternLoop(_Loop):
    """A plan request over RPC for a pattern the cache has never seen."""

    def setup(self, engine) -> dict:
        from repro.launch.rpc import PlanRPCClient
        from repro.sparse.csr import CSRMatrix

        pc = time.perf_counter
        t = self.traffic
        self.engine = engine
        t0 = pc()
        n, u, v = deploy.stencil_edges(self.config)

        def make(rng, count, tag):
            mats = []
            for j in range(count):
                keep = rng.random(u.shape[0]) >= float(t["edge_drop"])
                pat = deploy.pattern(n, u[keep], v[keep])
                data = deploy.values(pat, rng, t["weight"], t["shift"])
                mats.append(CSRMatrix(pat.indptr, pat.indices, data, (n, n),
                                      f"{self.config['name']}-{tag}{j}"))
            return mats

        self.mats = make(_rng(self.seed, 0), int(t["patterns"]), "p")
        warm = make(_rng(self.seed, 1), int(t["warmup_requests"]), "w")
        t_inputs = pc() - t0
        t0 = pc()
        self.server = engine.serve(rpc=True, host="127.0.0.1", port=0)
        self.client = PlanRPCClient("127.0.0.1", self.server.port,
                                    timeout=float(t["timeout_s"]))
        for a in warm:
            self.client.plan_detailed(a)
        t_warm = pc() - t0
        return {"inputs": t_inputs, "warm-up": t_warm}

    def _send(self, i: int) -> dict:
        resp = self.client.plan_detailed(self.mats[i])
        return {"j": i, "plan": resp["plan"],
                "spans": {k: v / 1e3 for k, v in resp["spans_ms"].items()}}

    def run(self, seconds: float) -> Window:
        return self._window(seconds, self._send, len(self.mats))

    def close(self) -> None:
        client, self.client = getattr(self, "client", None), None
        server, self.server = getattr(self, "server", None), None
        if client is not None:
            client.close()
        if server is not None:
            # closing the listener does not wake the server's accept thread
            # (a daemon), which close() would otherwise wait 30 s for
            server.close(timeout=2.0)

    def check(self, records) -> list:
        """Every plan: a permutation whose symbolic factor is the exact one
        of the permuted pattern, a fill near a minimum-degree ordering's,
        and the ordering the selector's host path picks."""
        done = [r for r in records if r["ok"]]
        lim = self.limits
        if not done:
            return []
        bad_sym = 0
        ratios = []
        for r in done:
            a = self.mats[r["j"]]
            plan = r["plan"]
            if not reference.is_permutation(plan.perm, a.n):
                bad_sym += 1
                continue
            parent, counts = reference.symbolic(a.indptr, a.indices,
                                                plan.perm)
            if not (np.array_equal(parent, plan.sym.parent)
                    and np.array_equal(counts, plan.sym.counts)
                    and int(counts.sum()) == plan.nnz_L):
                bad_sym += 1
            ratios.append(int(counts.sum()) / reference.min_degree_nnz_l(
                reference.matrix(a.indptr, a.indices, a.data)))
        host, _ = self.engine.selector.select_batch(
            [self.mats[r["j"]] for r in done], path="host")
        mismatch = sum(1 for r, h in zip(done, host)
                       if r["plan"].algorithm != h)
        fill = max(ratios) if ratios else float("inf")
        return [_check("symbolic_mismatch", bad_sym, 0, bad_sym == 0),
                _check("fill_ratio_max", fill, lim["fill_ratio_max"],
                       fill <= lim["fill_ratio_max"]),
                _check("ordering_mismatch", mismatch, 0, mismatch == 0)]


KINDS = {"refactor": RefactorLoop, "new_pattern": NewPatternLoop}


def make_loop(cell: dict, seed: int):
    kind = cell["traffic"]["kind"]
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} has no loop (have "
                         f"{sorted(KINDS)})")
    return KINDS[kind](cell["config"], cell["traffic"], seed)
