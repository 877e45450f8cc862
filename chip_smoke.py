#!/usr/bin/env python3
"""Chip smoke test: the solver's main path, end to end, on one TPU.

    python chip_smoke.py              # one chip: train → serve → solve
    python chip_smoke.py --chips 4    # four chips: sharded featurize → infer

With no option every phase runs in this one process on
``jax.devices()[0]``, through the entry points a user calls:

* **device** — the platform must be ``tpu``; anything else exits non-zero
  naming it (no CPU fallback, no interpret mode).
* **train** — a random-forest :class:`SolverEngine` (``path="device"``,
  Pallas featurization, ``backend="pipelined"``, ``sweep="device"``,
  ``solve_dtype="fp32_refine"``) is fitted on the committed labels
  ``artifacts/labels_c36_s7_x0.35_r1.npz``, with a fresh plan cache under
  the output directory.
* **serve** — ``engine.serve(rpc=True)`` on 127.0.0.1; an in-process client
  thread (it initializes no JAX backend of its own) sends 16 ``plan``
  requests over 4 structures. Every warm request must be a cache hit, and
  every served algorithm must equal the host-path selector's choice.
* **solve** — ``engine.solve`` on a 2-D grid (150², n = 22,500) and a 3-D
  grid (24³, n = 13,824) with 1 and 8 right-hand sides. Each ``x`` must
  reach a relative residual ≤ 1e-10 with a converged refinement and agree
  with the host fp64 reference (``backend="numpy"``, ``sweep="seq"``) on
  the same plan.

``--chips 4`` runs only the sharded featurize → infer phase: a 4-device
serving mesh against the 1-device mesh in the same process.

Lines before the last are progress and smoke timings — wall times of one
run including compilation, not benchmark results. The last line is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
A failed phase raises, so the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LABELS = os.path.join(REPO, "artifacts", "labels_c36_s7_x0.35_r1.npz")

#: solve acceptance: relative residual of every solve, and relative
#: distance of x to the host fp64 reference (the grids are diagonally
#: dominant, κ ≈ 10, so the forward error tracks the residual)
RESIDUAL_TOL = 1e-10
AGREE_TOL = 1e-9
#: --chips 4: features of the 4- and 1-device meshes agree to this relative
#: tolerance (each shard runs the same kernels on a slice of the batch;
#: XLA may fuse a reduction differently per program)
FEATURE_RTOL = 1e-6


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[smoke:{phase}] {msg}", flush=True)


def timing(phase: str, what: str, seconds: float) -> None:
    log(phase, f"smoke timing (one run, compile included; not a benchmark "
               f"result): {what} {seconds:.3f} s")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(chips: int = 1) -> dict:
    """The device JAX reports; raises unless it is a TPU with ``chips``
    devices or more."""
    import jax

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"JAX's default device is on platform {d.platform!r} "
          f"({d.device_kind}), not 'tpu': this smoke runs on a TPU only — "
          f"no CPU fallback, no interpret mode")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devs)}")
    log("device", f"{d.platform} / {d.device_kind} x{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def train_phase(out_dir: str, labels: str = LABELS, **config):
    """A trained main-path engine; plan cache, autotune records and
    bundles all live fresh under ``out_dir``. ``config`` overrides
    :class:`EngineConfig` fields."""
    from repro.core.labeling import LabeledDataset
    from repro.engine import EngineConfig, SolverEngine

    for sub in ("plan_cache", "autotune", "bundles"):
        shutil.rmtree(os.path.join(out_dir, sub), ignore_errors=True)
    kw = dict(model="random_forest", path="device", use_pallas=True,
              backend="pipelined", sweep="device",
              solve_dtype="fp32_refine",
              cache_dir=os.path.join(out_dir, "plan_cache"),
              autotune_dir=os.path.join(out_dir, "autotune"),
              bundle_dir=os.path.join(out_dir, "bundles"),
              fast_grids=True, cv=3, seed=0)
    kw.update(config)
    engine = SolverEngine(EngineConfig(**kw))
    t0 = time.perf_counter()
    report = engine.train(LabeledDataset.load(labels))
    timing("train", "fit", time.perf_counter() - t0)
    log("train", f"test accuracy {report['test_accuracy']:.3f} on "
                 f"{os.path.basename(labels)}; fingerprint "
                 f"{engine.fingerprint[:16]}")
    return engine


def serve_phase(engine, mats, repeats: int = 4,
                timeout_s: float = 1800.0) -> list:
    """``repeats`` rounds of one ``plan`` request per structure over the
    RPC front-end; round 1 is cold, the rest must be cache hits. Returns
    the served algorithm per structure."""
    from repro.launch.rpc import PlanRPCClient

    host_names, _ = engine.selector.select_batch(mats, path="host")
    server = engine.serve(rpc=True, host="127.0.0.1", port=0)
    served: dict = {}
    errors: list = []

    def client():
        try:
            with PlanRPCClient("127.0.0.1", server.port,
                               timeout=timeout_s) as c:
                for rnd in range(repeats):
                    t0 = time.perf_counter()
                    for i, m in enumerate(mats):
                        plan = c.plan(m)
                        served.setdefault(rnd, []).append(plan.algorithm)
                        check(sorted(plan.perm.tolist()) == list(range(m.n)),
                              f"plan for {m.name} is not a permutation")
                    served[f"t{rnd}"] = time.perf_counter() - t0
                    served[f"hits{rnd}"] = c.stats()["warm_hits"]
        except BaseException as exc:  # re-raised in the main thread
            errors.append(exc)

    try:
        th = threading.Thread(target=client, name="smoke-rpc-client")
        th.start()
        th.join(timeout_s)
        check(not th.is_alive(), f"RPC client still running after "
                                 f"{timeout_s:.0f} s")
    finally:
        server.close()
    if errors:
        raise errors[0]
    n = len(mats)
    check(served["hits0"] == 0,
          f"cold round served {served['hits0']} cache hits from a fresh "
          f"cache")
    for rnd in range(1, repeats):
        check(served[f"hits{rnd}"] == rnd * n,
              f"warm round {rnd}: {served[f'hits{rnd}'] - (rnd - 1) * n}/{n} "
              f"requests were cache hits")
        check(served[rnd] == served[0],
              f"warm round {rnd} served {served[rnd]}, cold {served[0]}")
    check(served[0] == list(host_names),
          f"device-path choices {served[0]} differ from the host path's "
          f"{list(host_names)}")
    timing("serve", f"cold round ({n} plans)", served["t0"])
    timing("serve", f"warm round ({n} plans)", served[f"t{repeats - 1}"])
    log("serve", f"{repeats * n} requests, {(repeats - 1) * n} warm hits; "
                 f"choices {dict(zip((m.name for m in mats), served[0]))} "
                 f"equal the host path's")
    return served[0]


def _padded_fronts(plan) -> tuple:
    """(largest padded front M, largest true front m) of the plan's level
    schedule under the engine's default pad policy."""
    from repro.sparse.schedule import build_schedule
    from repro.sparse.symbolic import supernodes

    sp, so = supernodes(plan.sym, relax=8)
    sched = build_schedule(plan.sym, sp, so)
    return (max(b.M for lv in sched.buckets for b in lv),
            max(fp.m for fp in sched.fronts))


def solve_phase(engine, cases, rhs=(1, 8), seed: int = 0) -> list:
    """``engine.solve`` on each ``(label, matrix)`` with each RHS count;
    the first 1-RHS solve of a matrix is repeated to show a warm time.
    Every solve must converge to ``RESIDUAL_TOL`` and agree with the host
    fp64 reference. Returns one record per solve."""
    import numpy as np

    from repro.core.plan import execute_plan

    rng = np.random.default_rng(seed)
    out = []
    for label, a in cases:
        plan = engine.plan(a)
        big, true_big = _padded_fronts(plan)
        log("solve", f"{label}: n={a.n} nnz={a.nnz} ordering "
                     f"{plan.algorithm}, largest front {true_big} "
                     f"(padded {big})")
        runs = [(k, tag) for k in rhs
                for tag in (("cold", "warm") if k == rhs[0] else ("cold",))]
        for k, tag in runs:
            b = rng.standard_normal(a.n if k == 1 else (a.n, k))
            t0 = time.perf_counter()
            r = engine.solve(a, b)
            dt = time.perf_counter() - t0
            ref = execute_plan(a, plan, b, backend="numpy", sweep="seq",
                               solve_dtype="fp64")
            agree = float(np.linalg.norm(r["x"] - ref["x"])
                          / np.linalg.norm(ref["x"]))
            rec = dict(matrix=label, n=a.n, k=k, run=tag,
                       algorithm=r["algorithm"], residual=r["residual"],
                       agree=agree, iters=r["refine_iterations"],
                       converged=r["refine_converged"],
                       residual_path=r["refine_residual"],
                       padded_front=big, seconds=dt)
            out.append(rec)
            log("solve", f"{label} k={k} ({tag}): residual "
                         f"{r['residual']:.3e}, |x-x_ref|/|x_ref| "
                         f"{agree:.3e}, refinement {r['refine_iterations']} "
                         f"iterations on the {r['refine_residual']} "
                         f"residual path, converged "
                         f"{r['refine_converged']}")
            timing("solve", f"{label} k={k} {tag} solve", dt)
            check((r["backend"], r["sweep"], r["solve_dtype"])
                  == ("pipelined", "device", "fp32_refine"),
                  f"solve ran {r['backend']}/{r['sweep']}/"
                  f"{r['solve_dtype']}, not the main path")
            check(r["refine_converged"] is True,
                  f"{label} k={k}: refinement did not converge "
                  f"(residual {r['residual']:.3e})")
            check(r["residual"] <= RESIDUAL_TOL,
                  f"{label} k={k}: residual {r['residual']:.3e} > "
                  f"{RESIDUAL_TOL:g}")
            check(agree <= AGREE_TOL,
                  f"{label} k={k}: x differs from the host fp64 reference "
                  f"by {agree:.3e} > {AGREE_TOL:g}")
    return out


def mesh_phase(engine, mats, n_devices: int = 4) -> list:
    """Sharded featurize → infer on an ``n_devices`` serving mesh against
    the 1-device mesh: identical choices, features within
    ``FEATURE_RTOL``. Returns the choices."""
    import numpy as np

    from repro.core.features import extract_features_batch_jnp, pad_csr_batch
    from repro.distributed.meshctx import make_serving_mesh, serving_mesh

    sel = engine.selector
    batch = pad_csr_batch(mats, bucket=True)
    got = {}
    for nd in (1, n_devices):
        with serving_mesh(make_serving_mesh(nd)):
            t0 = time.perf_counter()
            feats = np.asarray(extract_features_batch_jnp(batch,
                                                          use_pallas=True))
            names, _ = sel.select_batch(mats, path="device",
                                        use_pallas=True)
            got[nd] = (feats, names, time.perf_counter() - t0)
        timing("mesh", f"{nd}-device featurize+infer of {len(mats)} "
                       f"matrices", got[nd][2])
    f1, n1, _ = got[1]
    fn, nn, _ = got[n_devices]
    rel = float(np.max(np.abs(fn - f1) / np.maximum(np.abs(f1), 1.0)))
    check(nn == n1, f"{n_devices}-device choices {nn} differ from the "
                    f"1-device mesh's {n1}")
    check(rel <= FEATURE_RTOL, f"features differ by {rel:.3e} relative "
                               f"(> {FEATURE_RTOL:g})")
    log("mesh", f"{n_devices}-device mesh == 1-device mesh: {len(nn)} "
                f"identical choices, features within {rel:.3e} relative")
    return nn


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def serve_structures():
    """Four distinct structures for the serve phase: the two solve
    matrices and two from other families of the suite generator."""
    import numpy as np

    from repro.sparse.dataset import banded, grid2d, grid3d, scalefree

    rng = np.random.default_rng(11)
    return [grid2d(150, 150, "grid2d_150x150"),
            grid3d(24, 24, 24, "grid3d_24x24x24"),
            banded(6000, 12, 0.4, rng, "banded_6000"),
            scalefree(5000, 3, rng, "scalefree_5000")]


def mesh_structures():
    """A 16-matrix batch for the mesh phase: the serve structures and
    twelve suite matrices."""
    from repro.sparse.dataset import generate_suite

    return serve_structures() + list(generate_suite(count=12, seed=3,
                                                    size_scale=0.35))


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="On-chip smoke test of the solver's main path.")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the sharded featurize → infer phase "
                        "on a 4-device mesh (needs 4 chips)")
    p.add_argument("--out", default=os.path.join(REPO, ".chip_smoke"),
                   help="working directory for the plan cache and bundles")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        device = device_phase(args.chips)
    except SmokeFailure as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import configure_compile_cache

    log("device", f"compile cache: {configure_compile_cache(REPO)}")
    os.makedirs(args.out, exist_ok=True)
    t_all = time.perf_counter()
    engine = train_phase(args.out)
    if args.chips == 4:
        mesh_phase(engine, mesh_structures(), n_devices=4)
    else:
        mats = serve_structures()
        serve_phase(engine, mats)
        solve_phase(engine, [("grid2d 150x150", mats[0]),
                             ("grid3d 24^3", mats[1])])
    timing("all", "whole run", time.perf_counter() - t_all)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
