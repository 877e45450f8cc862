"""The per-layer readers of the program's finer spans, on a synthetic
window: each reads the mean of its span over the completed requests, and
reads nothing where the program records no such span."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

#: metric -> the span it reads
READERS = {
    "factor.schedule_ms": "factor.schedule",
    "factor.routes_ms": "factor.routes",
    "factor.compile_ahead_ms": "factor.compile_ahead",
    "factor.drain_ms": "factor.drain",
    "solve.sweep_setup_ms": "solve.sweep.setup",
    "plan.reorder_ms": "reorder",
    "plan.symbolic_ms": "symbolic",
}


@pytest.mark.parametrize("metric,stage", sorted(READERS.items()))
def test_reader_means_its_span(metric, stage):
    m, = [m for m in SPEC["per_layer"] if m["name"] == metric]
    assert m["source"] == "program_span" and m["unit"] == "ms"
    read = harness.load_reader(metric)
    records = [{"ok": True, "spans": {stage: 0.010, "other": 1.0}},
               {"ok": True, "spans": {stage: 0.030}},
               {"ok": False, "spans": {stage: 9.0}},    # unanswered
               {"ok": True, "spans": {"other": 2.0}}]  # no such span
    assert read(harness.RunView(None, records)) == pytest.approx(20.0)
    # a program without the span (the commit before it) reads nothing
    assert read(harness.RunView(None, [{"ok": True,
                                        "spans": {"other": 1.0}}])) is None
