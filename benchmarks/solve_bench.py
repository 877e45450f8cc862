"""Numeric-solve benchmark: the perf gate for the level-scheduled backends.

PR 2's e2e benchmark showed >95% of warm-path time is the numeric
factorization, so this is the trajectory that matters now. Per matrix ×
backend (numpy / per-front pallas / level-batched / pipelined):

* cold (first call, includes kernel compilation) and warm factor+solve
  wall times, residuals,
* achieved GFLOP/s against the **symbolic flop model**
  (``SymbolicFactor.flops`` — exact) and the dense-front flop count
  (``LevelSchedule`` — includes amalgamation padding; the ratio of the two
  is the structural overhead the supernode relaxation chose),
* per-level batch occupancy and fronts-per-level (the parallelism the
  batched backend can actually exploit),
* roofline terms (compute vs memory seconds from the flop model + front
  bytes) consumed by ``benchmarks/roofline.py``,
* for the batched/pipelined backends: the **overlap efficiency** (host
  assembly seconds over assembly + device-blocked seconds — the fraction
  of overlappable time the backend kept the host busy) and the solve-stage
  split (assemble/dispatch/sync),
* for the batched backend: the fp32 residual and the fp32+fp64-refinement
  residual/iterations,
* when both run: the max-abs solution difference pipelined vs batched
  (the two share every kernel, so this is 0.0 up to nondeterminism-free
  reordering — the parity gate),
* for the pipelined backend: the **device-sweep leg** — warm
  ``sweep="device"`` vs host ``"level"`` single-RHS times, raw and
  *refined* device-vs-host solution parity (the sweeps are f32, so the
  gated comparison is after fp64 refinement on both sides), the
  device-resident refinement residual/iterations, and the multi-RHS
  record: one ``(n, k)`` device solve vs ``k`` per-vector host level
  sweeps, with the achieved sweep GFLOP/s from
  ``LevelSchedule.sweep_flops``.

Emits ``BENCH_solve.json`` and exits non-zero when a gate fails:
``--gate-residual-fp64`` (numpy backend), ``--gate-residual-refine``
(batched + refinement), ``--gate-flop-ratio`` (dense-front flops vs
symbolic model drift), ``--gate-pipelined-parity`` (solution drift vs
batched), ``--gate-overlap-margin`` (pipelined overlap efficiency must
reach this fraction of the batched baseline), ``--gate-device-parity``
(refined device-sweep vs refined host-sweep solution drift), and
``--gate-rhs-speedup`` (suite-mean multi-RHS device throughput over
per-vector host sweeps). CI runs ``--quick`` on the interpret backend and
uploads the JSON as the second ``BENCH_*`` trajectory artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from repro.sparse.dataset import (banded, block_arrow, grid2d,
                                  permuted_banded, scalefree)
from repro.sparse.multifrontal import (factor_and_solve_timed,
                                       multifrontal_cholesky,
                                       multifrontal_solve)
from repro.sparse.refine import refine_solve, refine_solve_device
from repro.sparse.schedule import build_schedule
from repro.sparse.symbolic import symbolic_cholesky

#: Published peak rates per device kind (``jax.devices()[0].device_kind``),
#: each with its source. The front kernels contract f32 at HIGHEST
#: precision, i.e. several bf16 MXU passes, so the bf16 roof bounds them
#: from above. A kind missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": dict(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}
BYTES_PER_FRONT_CELL = 4 * 2   # f32 workspace, read + write
NOT_MEASURED = "not measured"


def device_peaks() -> Optional[Dict]:
    """The :data:`PEAKS` entry of the device JAX runs on, or None on the
    CPU (interpret-mode kernels: no roofline share is measured there).
    Raises for an accelerator kind the table lacks."""
    import jax

    d = jax.devices()[0]
    if d.platform == "cpu":
        return None
    if d.device_kind not in PEAKS:
        raise SystemExit(f"no published peaks for device kind "
                         f"{d.device_kind!r} ({d.platform}); add them to "
                         f"PEAKS with their source")
    return PEAKS[d.device_kind]


def make_suite(scale: float, rng: np.random.Generator) -> List:
    d = lambda base: max(4, int(round(base * scale)))
    return [
        grid2d(d(16), d(16), "grid2d"),
        banded(d(300), 4, 0.8, rng, "banded"),
        permuted_banded(d(300), 3, 0.85, rng, "pbanded"),
        scalefree(d(260), 2, rng, "scalefree"),
        block_arrow(max(4, int(4 * scale)), d(24), 8, rng, "block_arrow"),
    ]


def _best(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_sweeps(a, sym, sched, b, repeats: int, rhs_k: int = 8) -> Dict:
    """The device-sweep leg: warm level vs device single-RHS, refined
    parity, device-resident refinement, and the multi-RHS throughput
    record (one (n, k) device dispatch vs k per-vector host sweeps)."""
    rng = np.random.default_rng(1)
    f = multifrontal_cholesky(a, sym, backend="pipelined")
    B = rng.standard_normal((a.n, rhs_k))
    # warm-up: compile the device sweep buckets for both RHS widths
    xl = multifrontal_solve(f, b, mode="level")
    xd = multifrontal_solve(f, b, mode="device")
    multifrontal_solve(f, B, mode="device")
    denom = max(float(np.abs(xl).max()), 1e-30)
    xh, _ = refine_solve(a.matvec,
                         lambda r_: multifrontal_solve(f, r_, mode="level"),
                         b)
    xdr, info = refine_solve_device(a, f, b)
    level_s = _best(lambda: multifrontal_solve(f, b, mode="level"), repeats)
    device_s = _best(lambda: multifrontal_solve(f, b, mode="device"),
                     repeats)
    device_multi_s = _best(lambda: multifrontal_solve(f, B, mode="device"),
                           repeats)
    host_pervec_s = _best(
        lambda: [multifrontal_solve(f, B[:, j], mode="level")
                 for j in range(rhs_k)], repeats)
    return dict(
        rhs_k=rhs_k,
        level_s=level_s, device_s=device_s,
        device_multi_s=device_multi_s, host_pervec_s=host_pervec_s,
        multi_rhs_speedup=host_pervec_s / max(device_multi_s, 1e-12),
        sweep_gflops=sched.sweep_flops(rhs_k)
        / max(device_multi_s, 1e-12) / 1e9,
        raw_parity=float(np.abs(xd - xl).max()) / denom,     # f32 floor
        refined_parity=float(np.abs(xdr - xh).max())
        / max(float(np.abs(xh).max()), 1e-30),
        residual_device_refined=info.final_residual,
        refine_iterations_device=info.iterations,
        refine_converged_device=info.converged,
    )


def bench_matrix(a, backends: List[str], repeats: int,
                 peaks: Optional[Dict] = None) -> Dict:
    rng = np.random.default_rng(0)
    b = rng.standard_normal(a.n)
    t0 = time.perf_counter()
    sym = symbolic_cholesky(a)
    t_sym = time.perf_counter() - t0
    sched = build_schedule(sym)
    s = sched.stats()
    front_bytes = sum(fp.m * fp.m for fp in sched.fronts) * BYTES_PER_FRONT_CELL
    rec: Dict = dict(
        name=a.name, n=a.n, nnz=a.nnz, t_symbolic=t_sym,
        nsup=s["nsup"], nlevels=s["nlevels"],
        max_level_width=s["max_level_width"],
        fronts_per_level=s["nsup"] / max(s["nlevels"], 1),
        occupancy=s["occupancy"], nbatches=s["nbatches"],
        per_level_occupancy=s["per_level_occupancy"],
        min_level_occupancy=s["min_level_occupancy"],
        pad=s["pad"],
        sym_flops=sym.flops, front_flops=s["front_flops"],
        flop_ratio=s["front_flops"] / max(sym.flops, 1),
        roofline=(dict(front_bytes=front_bytes,
                       compute_s=NOT_MEASURED, memory_s=NOT_MEASURED)
                  if peaks is None else dict(
                      front_bytes=front_bytes,
                      compute_s=s["front_flops"] / peaks["flops"],
                      memory_s=front_bytes / peaks["hbm_bw"])),
        backends={},
    )
    for backend in backends:
        t0 = time.perf_counter()
        r = factor_and_solve_timed(a, b, sym=sym, backend=backend)
        cold = time.perf_counter() - t0
        warm = r
        for _ in range(max(repeats - 1, 0)):
            rr = factor_and_solve_timed(a, b, sym=sym, backend=backend)
            if rr["t_factor"] + rr["t_solve"] < warm["t_factor"] + warm["t_solve"]:
                warm = rr
        entry = dict(
            cold_s=cold,
            warm_factor_s=warm["t_factor"], warm_solve_s=warm["t_solve"],
            warm_s=warm["t_factor"] + warm["t_solve"],
            residual=warm["residual"],
            gflops=s["front_flops"] / max(warm["t_factor"], 1e-12) / 1e9,
        )
        # level-scheduled backends report their solve-stage split and the
        # overlap metric the pipelined gate runs on
        for k in ("t_factor_assemble", "t_factor_dispatch", "t_factor_sync",
                  "overlap_efficiency"):
            if k in warm:
                entry[k] = warm[k]
        if backend == "batched":
            f = multifrontal_cholesky(a, sym, backend="batched")
            t0 = time.perf_counter()
            _, info = refine_solve(a.matvec,
                                   lambda r_: multifrontal_solve(f, r_), b)
            entry["refine_s"] = time.perf_counter() - t0
            entry["residual_refined"] = info.final_residual
            entry["refine_iterations"] = info.iterations
            entry["refine_converged"] = info.converged
        rec["backends"][backend] = entry
    bk = rec["backends"]
    if "batched" in bk and "pallas" in bk:
        rec["speedup_batched_vs_pallas"] = (bk["pallas"]["warm_factor_s"]
                                            / max(bk["batched"]["warm_factor_s"],
                                                  1e-12))
    if "batched" in bk and "numpy" in bk:
        rec["speedup_batched_vs_numpy"] = (bk["numpy"]["warm_factor_s"]
                                           / max(bk["batched"]["warm_factor_s"],
                                                 1e-12))
    if "batched" in bk and "pipelined" in bk:
        rec["speedup_pipelined_vs_batched"] = (
            bk["batched"]["warm_factor_s"]
            / max(bk["pipelined"]["warm_factor_s"], 1e-12))
        # parity: both paths run the same kernels, so the factors agree to
        # the last bit — compare the solutions directly
        fb = multifrontal_cholesky(a, sym, backend="batched")
        fp_ = multifrontal_cholesky(a, sym, backend="pipelined")
        xb = multifrontal_solve(fb, b)
        xp = multifrontal_solve(fp_, b)
        denom = max(float(np.abs(xb).max()), 1e-30)
        rec["pipelined_parity_maxdiff"] = float(np.abs(xp - xb).max()) / denom
    if "pipelined" in bk:
        rec["sweeps"] = bench_sweeps(a, sym, sched, b, repeats)
    return rec


def run_gates(records: List[Dict], args) -> List[str]:
    fails: List[str] = []
    for r in records:
        bk = r["backends"]
        if "numpy" in bk and bk["numpy"]["residual"] > args.gate_residual_fp64:
            fails.append(f"{r['name']}: numpy residual "
                         f"{bk['numpy']['residual']:.2e} > "
                         f"{args.gate_residual_fp64:.0e}")
        if "batched" in bk:
            rb = bk["batched"]
            if rb["residual_refined"] > args.gate_residual_refine:
                fails.append(f"{r['name']}: batched+refine residual "
                             f"{rb['residual_refined']:.2e} > "
                             f"{args.gate_residual_refine:.0e}")
        # the dense-front cubic model can sit a hair under the per-column
        # symbolic sum on fundamental supernodes; amalgamation (relax=8)
        # legitimately pads a few ×. Outside [0.8, gate] means the supernode
        # partition or the flop accounting drifted.
        ratio = r["flop_ratio"]
        if not (0.8 <= ratio <= args.gate_flop_ratio):
            fails.append(f"{r['name']}: front/symbolic flop ratio {ratio:.2f} "
                         f"outside [0.8, {args.gate_flop_ratio}]")
        if "pipelined_parity_maxdiff" in r:
            d = r["pipelined_parity_maxdiff"]
            if d > args.gate_pipelined_parity:
                fails.append(f"{r['name']}: pipelined vs batched solution "
                             f"drift {d:.2e} > "
                             f"{args.gate_pipelined_parity:.0e}")
        bkk = r["backends"]
        if "batched" in bkk and "pipelined" in bkk:
            ob = bkk["batched"].get("overlap_efficiency")
            op = bkk["pipelined"].get("overlap_efficiency")
            if (ob is not None and op is not None
                    and op < ob * args.gate_overlap_margin):
                fails.append(
                    f"{r['name']}: pipelined overlap efficiency {op:.2f} "
                    f"< {args.gate_overlap_margin:.2f}× batched baseline "
                    f"{ob:.2f}")
        if "sweeps" in r:
            sw = r["sweeps"]
            if sw["refined_parity"] > args.gate_device_parity:
                fails.append(f"{r['name']}: refined device-sweep vs "
                             f"host-sweep drift {sw['refined_parity']:.2e} "
                             f"> {args.gate_device_parity:.0e}")
    # throughput is gated on the suite mean: tiny matrices pay fixed
    # dispatch overhead per call, the wide ones amortize it
    sp = [r["sweeps"]["multi_rhs_speedup"] for r in records
          if "sweeps" in r]
    if sp and float(np.mean(sp)) < args.gate_rhs_speedup:
        fails.append(f"multi-RHS device sweep speedup mean "
                     f"{float(np.mean(sp)):.2f}× < "
                     f"{args.gate_rhs_speedup:.2f}× over per-vector "
                     f"host sweeps")
    return fails


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--scale", type=float, default=1.0,
                   help="suite size multiplier")
    p.add_argument("--quick", action="store_true",
                   help="CI mode: small suite, fewer repeats")
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--backends", default="numpy,pallas,batched,pipelined",
                   help="comma-separated: numpy,pallas,batched,pipelined")
    p.add_argument("--out", default="BENCH_solve.json")
    p.add_argument("--gate-residual-fp64", type=float, default=1e-10)
    p.add_argument("--gate-residual-refine", type=float, default=1e-6)
    p.add_argument("--gate-flop-ratio", type=float, default=6.0)
    p.add_argument("--gate-pipelined-parity", type=float, default=1e-6,
                   help="max relative solution drift pipelined vs batched")
    # the pipelined backend defers every device wait to one drain, so its
    # overlap efficiency should dominate batched's blocking loop; the
    # margin < 1 absorbs scheduler jitter on tiny CI matrices
    p.add_argument("--gate-overlap-margin", type=float, default=0.75,
                   help="pipelined overlap efficiency must be ≥ margin × "
                        "the batched baseline")
    # the sweeps are f32, so parity is gated after fp64 refinement on both
    # sides — the raw f32 floor (~1e-7) is recorded but not gated
    p.add_argument("--gate-device-parity", type=float, default=1e-6,
                   help="max refined device-sweep vs host-sweep drift")
    p.add_argument("--gate-rhs-speedup", type=float, default=1.5,
                   help="min suite-mean multi-RHS device throughput over "
                        "per-vector host level sweeps")
    p.add_argument("--no-gate", action="store_true")
    args = p.parse_args(argv)
    if args.quick:
        args.scale = min(args.scale, 0.6)
        args.repeats = min(args.repeats, 2)

    rng = np.random.default_rng(0)
    mats = make_suite(args.scale, rng)
    backends = [b.strip() for b in args.backends.split(",") if b.strip()]
    peaks = device_peaks()
    records = []
    for a in mats:
        rec = bench_matrix(a, backends, args.repeats, peaks)
        records.append(rec)
        line = (f"{rec['name']:>12s} n={rec['n']:>5d} nsup={rec['nsup']:>4d} "
                f"levels={rec['nlevels']:>3d} "
                f"f/lvl={rec['fronts_per_level']:.1f} "
                f"occ={rec['occupancy']:.2f}")
        for be in backends:
            e = rec["backends"][be]
            line += f" | {be} {e['warm_s']*1e3:8.2f}ms r={e['residual']:.1e}"
        print(line)
    doc = dict(
        bench="solve", scale=args.scale, repeats=args.repeats,
        backends=backends,
        peak_flops=None if peaks is None else peaks["flops"],
        hbm_bw=None if peaks is None else peaks["hbm_bw"],
        peak_source=NOT_MEASURED if peaks is None else peaks["source"],
        records=records,
    )
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=2)
    print(f"wrote {args.out} ({len(records)} matrices)")

    wide = [r for r in records
            if r["fronts_per_level"] >= 4 and "speedup_batched_vs_pallas" in r]
    if wide:
        sp = [r["speedup_batched_vs_pallas"] for r in wide]
        print(f"batched vs per-front pallas on ≥4-fronts/level matrices: "
              f"min {min(sp):.1f}×, mean {float(np.mean(sp)):.1f}×")
    ov = [(r["backends"]["batched"].get("overlap_efficiency"),
           r["backends"]["pipelined"].get("overlap_efficiency"))
          for r in records
          if "batched" in r["backends"] and "pipelined" in r["backends"]]
    ov = [(b_, p_) for b_, p_ in ov if b_ is not None and p_ is not None]
    if ov:
        print(f"overlap efficiency (host-busy fraction): batched mean "
              f"{float(np.mean([b_ for b_, _ in ov])):.2f}, pipelined mean "
              f"{float(np.mean([p_ for _, p_ in ov])):.2f}")
    sw = [r["sweeps"] for r in records if "sweeps" in r]
    if sw:
        sp_ = [s["multi_rhs_speedup"] for s in sw]
        print(f"device sweeps: multi-RHS (k={sw[0]['rhs_k']}) speedup over "
              f"per-vector host sweeps min {min(sp_):.1f}×, mean "
              f"{float(np.mean(sp_)):.1f}×; sweep GFLOP/s mean "
              f"{float(np.mean([s['sweep_gflops'] for s in sw])):.3f}; "
              f"refined parity max "
              f"{max(s['refined_parity'] for s in sw):.1e}")

    if not args.no_gate:
        fails = run_gates(records, args)
        if fails:
            print("GATE FAILURES:")
            for f in fails:
                print("  " + f)
            return 1
        print("gates: OK (residuals + flop-ratio drift)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
