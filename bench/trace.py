"""From a JAX profiler trace to device busy time, kernel times and the idle
gaps by what the host was doing.

* The window is the host span ``bench.window`` that the traffic loop opens
  around the timed requests.
* Busy time is the union of the intervals of the device's operations (the
  ``XLA Ops`` line of each TPU plane) inside the window, averaged over the
  chips that ran anything; idle time is the rest of the window.
* A kernel's time is the sum of the durations of its events; kernels are
  found by the names the trace gives the Pallas calls (:data:`KERNELS`).
* Each idle gap is labelled by the innermost host span open on the window's
  thread at the gap's middle, and idle time is summed by label.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
from typing import Dict, List, Optional, Tuple

__all__ = ["KERNELS", "TraceSummary", "Tracer", "reduce_profile",
           "load_profile"]

#: kernel -> the HLO instruction names its events carry in a TPU trace. An
#: ``XLA Ops`` event is named by its HLO text, ``%<name>.<n> = ...
#: custom-call(...), custom_call_target="tpu_custom_call"``; the Pallas
#: calls have no ``name=`` of their own, so the custom call takes the name
#: of the jitted function around it (TPU v5 lite, jax 0.9.0). Each of these
#: functions holds exactly one Pallas call.
KERNELS: Dict[str, Tuple[str, ...]] = {
    "frontal_factor": ("%_factor_batch_ws_jit.",),
    "tri_solve": ("%_sweep_fwd_jit.", "%_sweep_bwd_jit.", "%_tri_solve_jit."),
    "extend_add": ("%_extend_add_impl.",),
}
PALLAS_CALL = 'custom_call_target="tpu_custom_call"'
WINDOW_SPAN = "bench.window"
DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    kernel_s: Dict[str, float]
    kernel_n: Dict[str, int]
    device_ops: List[list]        # [[name, seconds], ...] most time first
    idle_gaps: List[list]         # [[host span, idle seconds], ...]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def kernel_of(name: str) -> Optional[str]:
    """The kernel an ``XLA Ops`` event belongs to, or None."""
    if PALLAS_CALL not in name:
        return None
    head = name.split(" = ", 1)[0]
    for kernel, keys in KERNELS.items():
        if any(head.startswith(k) for k in keys):
            return kernel
    return None


def op_label(name: str, module: str) -> str:
    """A short label of a device op: its kernel, else ``module:op``."""
    kernel = kernel_of(name)
    if kernel is not None:
        return kernel
    op = name.split(" = ", 1)[0].lstrip("%")
    return f"{module.split('(', 1)[0]}:{op}"


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce_profile(profile, window_span: str = WINDOW_SPAN) -> TraceSummary:
    """Reduce a :class:`jax.profiler.ProfileData` to a summary of the
    window ``window_span`` (the longest such host span)."""
    host_line = None
    window = None
    for plane in profile.planes:
        if plane.name.startswith("/device"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == window_span and (window is None
                                            or e - s > window[1] - window[0]):
                    window, host_line = (s, e), line
    if window is None:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, w1 = window

    chips = []
    kernel_s: Dict[str, float] = {}
    kernel_n: Dict[str, int] = {}
    op_s: Dict[str, float] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device"):
            continue
        lines = {line.name: line for line in plane.lines}
        if DEVICE_LINE not in lines:
            continue
        mods = (sorted(_events(lines[MODULE_LINE]), key=lambda t: t[1])
                if MODULE_LINE in lines else [])
        ivs = []
        mi = 0
        for name, s, e in sorted(_events(lines[DEVICE_LINE]),
                                 key=lambda t: t[1]):
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            while mi < len(mods) and mods[mi][2] <= s:
                mi += 1
            module = (mods[mi][0] if mi < len(mods) and mods[mi][1] <= s
                      else "?")
            label = op_label(name, module)
            op_s[label] = op_s.get(label, 0.0) + (e - s) * 1e-9
            k = kernel_of(name)
            if k is not None:
                kernel_s[k] = kernel_s.get(k, 0.0) + (e - s) * 1e-9
                kernel_n[k] = kernel_n.get(k, 0) + 1
        if ivs:
            chips.append(_union(ivs))
    busy_ns = (sum(sum(e - s for s, e in u) for u in chips) / len(chips)
               if chips else 0.0)

    # idle gaps of the first chip that ran anything, labelled by the
    # innermost host span open at each gap's middle (spans of one thread
    # nest, so a stack sweep in time order finds it)
    host = sorted(((s, -e, n) for n, s, e in _events(host_line)
                   if e > w0 and s < w1))
    gaps: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []
    nxt = 0
    t = w0
    for s, e in (chips[0] if chips else []) + [(w1, w1)]:
        if s > t:
            mid = 0.5 * (s + t)
            while nxt < len(host) and host[nxt][0] <= mid:
                hs, neg_end, name = host[nxt]
                while stack and stack[-1][0] <= hs:
                    stack.pop()
                stack.append((-neg_end, name))
                nxt += 1
            while stack and stack[-1][0] <= mid:
                stack.pop()
            label = stack[-1][1] if stack else "(no host span)"
            gaps[label] = gaps.get(label, 0.0) + (s - t) * 1e-9
        t = max(t, e)
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])[:TOP]]
    return TraceSummary((w1 - w0) * 1e-9, busy_ns * 1e-9, kernel_s,
                        kernel_n, top(op_s), top(gaps))


def load_profile(log_dir: str):
    """The ProfileData of the one trace written under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {log_dir}, found "
                         f"{len(paths)}")
    return ProfileData.from_file(paths[0])


class Tracer:
    """The profiler around one window, and the reduction of its trace."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        """Trace the device and the host's own spans; the Python tracer,
        which would time every Python call, stays off."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self) -> TraceSummary:
        return reduce_profile(load_profile(self.log_dir))

    def cleanup(self) -> None:
        shutil.rmtree(self.log_dir, ignore_errors=True)
