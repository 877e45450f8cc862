"""The trace reduction, on a small trace recorded on a TPU v5 lite (one
pipelined factor + device sweeps + refinement of a 6 x 6 grid, committed
under data/) and on a made-up trace whose answer is known."""
import gzip
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

DATA = Path(__file__).parent / "data" / "solve_6x6.xplane.pb.gz"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _profile(host, ops, modules=()):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="main", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=list(modules)),
            NS(name="XLA Ops", events=ops)]),
    ])


CALL = ' = f32[2,8,8] custom-call(), custom_call_target="tpu_custom_call"'


def test_made_up_trace():
    host = [_ev("bench.window", 0, 1000), _ev("bench.request", 1, 998),
            _ev("PjitFunction(_factor_batch_ws_jit)", 100, 50),
            _ev("DevicePut", 600, 100)]
    ops = [_ev("%_factor_batch_ws_jit.1" + CALL, 200, 100),
           _ev("%fusion.3 = f32[8] fusion()", 250, 100),      # overlaps
           _ev("%_sweep_fwd_jit.1" + CALL, 500, 50),
           _ev("%_extend_add_impl.1" + CALL, 900, 200)]       # leaves window
    mods = [_ev("jit__factor_batch_ws_jit(123)", 190, 200),
            _ev("jit__sweep_fwd_jit(9)", 490, 100),
            _ev("jit__extend_add_impl(7)", 890, 300)]
    s = trace.reduce_profile(_profile(host, ops, mods))
    assert s.window_s == pytest.approx(1e-6)
    # busy: [200, 350) + [500, 550) + [900, 1000) = 300 ns
    assert s.busy_s == pytest.approx(300e-9)
    assert s.kernel_s == pytest.approx({"frontal_factor": 100e-9,
                                        "tri_solve": 50e-9,
                                        "extend_add": 100e-9})
    labels = dict(s.device_ops)
    assert labels["jit__factor_batch_ws_jit:fusion.3"] == pytest.approx(1e-7)
    gaps = dict(s.idle_gaps)
    # [0, 200) mid 100 -> the dispatch span; [350, 500) mid 425 and
    # [550, 900) mid 725 -> bench.request (DevicePut ended at 700)
    assert gaps["PjitFunction(_factor_batch_ws_jit)"] == pytest.approx(2e-7)
    assert gaps["bench.request"] == pytest.approx(5e-7)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_profile(_profile([_ev("other", 0, 10)], []))


def test_kernel_names():
    assert trace.kernel_of("%_sweep_bwd_jit.1" + CALL) == "tri_solve"
    assert trace.kernel_of("%_sweep_bwd_jit.1 = f32[8] add()") is None
    assert trace.kernel_of("%copy.1 = f32[8] copy()") is None


@pytest.fixture(scope="module")
def chip_trace():
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(gzip.decompress(DATA.read_bytes()))


def test_chip_trace(chip_trace):
    s = trace.reduce_profile(chip_trace)
    assert 0 < s.busy_s < s.window_s
    for k in ("frontal_factor", "tri_solve", "extend_add"):
        assert s.kernel_n.get(k, 0) > 0, k
    assert sum(s.kernel_s.values()) <= s.busy_s * (1 + 1e-9)
    assert sum(v for _, v in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
    assert len(s.device_ops) <= trace.TOP
    assert [v for _, v in s.device_ops] == sorted(
        (v for _, v in s.device_ops), reverse=True)

    # busy time again, by brute force over a 1-ns timeline of the window
    host = [e for p in chip_trace.planes if not p.name.startswith("/device")
            for line in p.lines for e in line.events
            if e.name == trace.WINDOW_SPAN]
    w0 = int(host[0].start_ns)
    w1 = int(host[0].start_ns + host[0].duration_ns)
    on = np.zeros(w1 - w0, bool)
    for p in chip_trace.planes:
        for line in p.lines:
            if p.name.startswith("/device") and line.name == "XLA Ops":
                for e in line.events:
                    a = max(int(e.start_ns), w0) - w0
                    b = min(int(e.start_ns + e.duration_ns), w1) - w0
                    if b > a:
                        on[a:b] = True
    assert s.busy_s == pytest.approx(on.sum() * 1e-9, rel=1e-3, abs=1e-8)
