"""Host structure work per factorization (ms): the self time of the
program's ``factor`` span, less its ``factor.assemble`` and ``factor.device``
parts: supernodes, the level schedule, the extend-add routes and plans,
and the look-ups of the compiled programs."""


def read(run):
    vals = [r["spans"]["factor"] - r["spans"].get("factor.assemble", 0.0)
            - r["spans"].get("factor.device", 0.0)
            for r in run.records
            if r.get("ok") and "factor" in r["spans"]]
    return 1e3 * sum(vals) / len(vals) if vals else None
