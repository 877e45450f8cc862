"""One run of one benchmark cell: set-up, a timed window, one result line.

``bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
resolves the cell in ``BENCHMARK.json`` to its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<mix>.json``, driven by :mod:`bench.traffic`) and its
per-layer metrics (``bench/metrics/<metric>.py``, each a ``read(run)``), so
a cell, a configuration, a mix or a metric is added by adding files.

The run refuses to start without a TPU (exit 3, no result) and without the
program beside it (exit 4). Progress goes to earlier lines; the last line
of standard output is the result, and the last lines of standard error
are the numbers ``correct`` was decided by, each beside its limit.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import logging
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
#: JAX's persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = ROOT / ".bench_cache" / "jax"
STATE_DIR = ROOT / ".bench_cache" / "engine"

__all__ = ["Refused", "load_cell", "check_device", "run_cell", "main"]


class Refused(Exception):
    """The run cannot take place; ``code`` is the exit code."""

    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def _read_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise Refused(2, f"missing benchmark file {path}") from None


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell ``workload`` with its configuration, traffic mix and the
    end-to-end and per-layer metrics it reports."""
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(2, f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    config = _read_json(root / "bench" / "configs" / f"{cell['config']}.json")
    traffic = _read_json(root / "bench" / "traffic"
                         / f"{cell['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [])
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    return dict(cell=cell, config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def load_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.exists():
        raise Refused(2, f"per-layer metric {name!r} has no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# device, compile cache, program
# ---------------------------------------------------------------------------

def check_device(chips: int) -> dict:
    """The device JAX reports; refuses anything but ``chips`` TPUs or more."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise Refused(3, f"JAX's device is {d.platform!r} ({d.device_kind})"
                         f", not a TPU: this benchmark runs on a TPU only")
    if len(devs) < chips:
        raise Refused(3, f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def configure_cache(path: Path = CACHE_DIR) -> Path:
    """Keep every compiled program, however fast it compiled, in a fixed
    directory of the checkout, so only a checkout's first run compiles."""
    import jax

    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter(logging.Filter):
    """Programs JAX compiled and programs it loaded from the persistent
    cache, counted from the cache's own hit and miss log records (which it
    writes at debug level, so the filter also keeps them off the logs),
    with the names of the programs that missed."""

    HIT = "Persistent compilation cache hit for '"
    MISS = "PERSISTENT COMPILATION CACHE MISS for '"

    def __init__(self):
        super().__init__()
        self.loaded = 0
        self.compiled = 0
        self.missed: list = []
        lg = logging.getLogger("jax._src.compiler")
        self._level = lg.getEffectiveLevel()
        lg.setLevel(logging.DEBUG)
        lg.addFilter(self)

    def filter(self, record) -> bool:
        msg = record.getMessage()
        if msg.startswith(self.HIT):
            self.loaded += 1
        elif msg.startswith(self.MISS):
            self.compiled += 1
            self.missed.append(msg[len(self.MISS):].split("'", 1)[0])
        return record.levelno >= self._level

    def snapshot(self) -> tuple:
        return self.compiled, self.loaded


def import_program(root: Path = ROOT) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(4, f"the program is not beside the benchmark "
                         f"({src / 'repro'} is missing)")
    sys.path.insert(0, str(src))


def build_engine(config: dict, root: Path = ROOT):
    """The configuration's engine, its selector trained on the labels the
    configuration names."""
    from repro.core.labeling import LabeledDataset
    from repro.engine import EngineConfig, SolverEngine

    kw = {k: v for k, v in config["engine"].items() if k != "labels"}
    kw.update(cache_dir=None,
              autotune_dir=str(STATE_DIR / "autotune"),
              bundle_dir=str(STATE_DIR / "bundles"))
    engine = SolverEngine(EngineConfig(**kw))
    engine.train(LabeledDataset.load(str(root / config["engine"]["labels"])))
    return engine


# ---------------------------------------------------------------------------
# what the readers see
# ---------------------------------------------------------------------------

class RunView:
    """One finished window, as the per-layer readers see it."""

    def __init__(self, loop, records, trace=None, work=None):
        self.loop = loop
        self.records = records            # per request: spans (s), info
        self.trace = trace                # bench.trace.TraceSummary
        self.work = work or {}            # kernel -> (flops, bytes, least s)

    def spans(self, stage: str) -> list:
        """Seconds of ``stage`` per completed request that recorded it."""
        return [r["spans"][stage] for r in self.records
                if r.get("ok") and stage in r["spans"]]

    def mean_ms(self, stage: str):
        vals = self.spans(stage)
        return 1e3 * sum(vals) / len(vals) if vals else None

    def roofline_pct(self, kernel: str):
        """The kernel's least time over its measured time, in percent;
        None where the trace holds none of its events."""
        if self.trace is None or kernel not in self.work:
            return None
        busy = self.trace.kernel_s.get(kernel, 0.0)
        if busy <= 0.0:
            return None
        return 100.0 * self.work[kernel][2] / busy

    def idle_pct(self):
        if self.trace is None or self.trace.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float, counter=None, loop=None,
             engine=None) -> dict:
    """Set-up, window, readers and checks of one run; the result object.
    ``loop`` replaces the traffic mix's own loop and ``engine`` the
    configuration's freshly trained one (the tests plant faults through
    them)."""
    from bench import traffic

    pc = time.perf_counter
    t_imports = pc() - t_start
    if loop is None:
        loop = traffic.make_loop(cell, seed)
    t0 = pc()
    if engine is None:
        engine = build_engine(cell["config"])
    t_train = pc() - t0
    phases = loop.setup(engine)
    setup_s = pc() - t_start
    compiled, loaded = counter.snapshot() if counter else (0, 0)
    log("set-up: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in
        [("imports", t_imports), ("train", t_train)] + list(phases.items()))
        + f"; total {setup_s:.3f} s; programs compiled {compiled}, "
          f"loaded from the cache {loaded}"
        + (f"; compiled: {sorted(set(counter.missed))}"
           if counter and 0 < compiled <= 20 else ""))

    tracer = None
    if trace:
        from bench import trace as tr
        tracer = tr.Tracer(tempfile.mkdtemp(prefix="bench-trace-"))
        tracer.start()
    try:
        window = loop.run(seconds)
    finally:
        if tracer is not None:
            tracer.stop()
    c2, l2 = counter.snapshot() if counter else (0, 0)
    log(f"window: {len(window.records)} requests in {window.seconds:.3f} s, "
        f"{c2 - compiled} programs compiled and {l2 - loaded} loaded inside "
        f"it (should be 0), "
        f"client late by at most {1e3 * window.max_late_s:.3f} ms "
        f"(mean {1e3 * window.mean_late_s:.3f} ms)")
    mem = memory_peak_bytes()
    done = [r for r in window.records if r["ok"]]
    failed = len(window.records) - len(done)

    dev = dict(device, memory_peak_bytes=mem)
    result = {"correct": False, "attempted": len(window.records),
              "failed": failed, "metrics": {}, "device": dev}
    if trace:
        summary = tracer.reduce()
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        work = loop.kernel_work(window.records, loop.peaks())
        view = RunView(loop, window.records, summary, work)
        for m in cell["per_layer"]:
            try:
                value = load_reader(m["name"])(view)
            except Exception as exc:  # a reader that fails reports nothing
                log(f"per-layer metric {m['name']}: no reading ({exc!r})")
                value = None
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
        tracer.cleanup()
    else:
        for m in cell["end_to_end"]:
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] == cell["traffic"]["end_to_end"]:
                value = (1e3 * window.seconds / len(done)) if done else None
            else:
                raise Refused(2, f"no measurement for end-to-end metric "
                                 f"{m['name']!r} in traffic "
                                 f"{cell['traffic']['name']!r}")
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    # a request that raised never answered: that is for ``correct`` too
    t0 = pc()
    checks = [{"name": "unanswered", "value": failed, "limit": 0,
               "ok": failed == 0}] + loop.check(window.records)
    log(f"checks of the window's answers: {pc() - t0:.3f} s")
    loop.close()
    result["correct"] = all(c["ok"] for c in checks) and len(done) > 0
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    return result


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload)
        device = check_device(int(cell["cell"]["chips"]))
        import_program()
    except Refused as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return exc.code
    log(f"compile cache: {configure_cache()}")
    counter = CompileCounter()
    log(f"cell {args.workload}: config {cell['config']['name']}, traffic "
        f"{cell['traffic']['name']}, seed {args.seed}, {args.seconds:g} s, "
        f"trace {args.trace}, device {device}")
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, t_start, counter)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} against limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
