"""Roofline view of the numeric solve, from ``BENCH_solve.json`` records.

Per matrix: the two roofline terms of the dense-front work
    compute = front FLOPs / peak FLOP/s
    memory  = front workspace bytes / peak HBM bytes/s
with the peaks the bench recorded for its device
(``benchmarks.solve_bench.PEAKS``, keyed by device kind), the dominant
bottleneck, and per backend the achieved GFLOP/s and its fraction of the
compute roof. A bench run on the CPU records no peaks; its roofline
columns read "not measured". Run ``benchmarks/solve_bench.py`` first to
produce the input; this is a pure formatter of its records.
"""
from __future__ import annotations

import json
import os
import sys

NOT_MEASURED = "not measured"

DEFAULT_PATH = os.environ.get("REPRO_BENCH_SOLVE", "BENCH_solve.json")


def load(path: str = DEFAULT_PATH) -> dict:
    with open(path) as fh:
        return json.load(fh)


def terms_of(rec: dict, doc: dict):
    """(terms in seconds, bottleneck name), or (None, None) without
    peaks."""
    if doc.get("peak_flops") is None:
        return None, None
    terms = dict(compute_s=rec["front_flops"] / doc["peak_flops"],
                 memory_s=rec["roofline"]["front_bytes"] / doc["hbm_bw"])
    return terms, max(terms, key=terms.get)


def fmt_row(rec: dict, backends, doc: dict) -> str:
    t, dom = terms_of(rec, doc)
    if t is None:
        roof = f"{NOT_MEASURED} | {NOT_MEASURED} | {NOT_MEASURED}"
    else:
        roof = (f"{t['compute_s']*1e6:.2f} | {t['memory_s']*1e6:.2f} | "
                f"**{dom.replace('_s', '')}**")
    cells = [f"| {rec['name']} | {rec['n']} | {roof} | "
             f"{rec['flop_ratio']:.2f} | {rec['occupancy']:.2f} "]
    for be in backends:
        e = rec["backends"].get(be)
        if e is None:
            cells.append("| — ")
            continue
        frac = (NOT_MEASURED if t is None
                else f"{e['gflops'] * 1e9 / doc['peak_flops'] * 100:.2g}%")
        cells.append(f"| {e['gflops']:.3f} ({frac}) ")
    return "".join(cells) + "|"


def main(path: str = DEFAULT_PATH) -> str:
    doc = load(path)
    backends = doc.get("backends", [])
    head = ["### Solve roofline — front work terms (µs) + achieved GFLOP/s",
            "",
            "| matrix | n | compute µs | memory µs | bottleneck | "
            "flops/symbolic | occupancy | "
            + " | ".join(f"{b} GF/s (of peak)" for b in backends) + " |",
            "|---" * (7 + len(backends)) + "|"]
    rows = [fmt_row(r, backends, doc) for r in doc["records"]]
    recs = doc["records"]
    best = max(recs, key=lambda r: max(e["gflops"]
                                       for e in r["backends"].values()))
    tail = ["",
            f"peak achieved: {best['name']} "
            f"({max(e['gflops'] for e in best['backends'].values()):.3f} "
            f"GFLOP/s); all records from {path}"]
    return "\n".join(head + rows + tail)


if __name__ == "__main__":
    print(main(sys.argv[1] if len(sys.argv) > 1 else DEFAULT_PATH))
