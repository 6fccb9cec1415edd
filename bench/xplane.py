"""Reduction of a JAX profiler trace to device busy time, idle share and the
``breakdown`` of the result line.

A device operation is an event on the ``XLA Ops`` line of a device plane
(``/device:TPU:<i>``). On the CPU backend, which has no device plane, it is
an event of a host thread that carries an ``hlo_op`` stat: that is how the
tests record a small trace without a chip. Busy time is the union of the
operations' intervals inside the window; an idle gap is a stretch of the
window with no operation. Each gap is named after the innermost benchmark
span (``bench.*``, written by ``jax.profiler.TraceAnnotation``) that holds
its midpoint, and after the device operation that ends it, so that a gap
before a sweep reads differently from one before a divide pass.

Busy time, each operation's time and each gap are averaged over the chips
the run used, whether or not a chip ran anything in the window: a chip
that idles throughout counts as idle, not as absent.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
TOP = 10
IDLE_CHIP = "chip idle all window"


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    end_ns: float
    module: str = ""


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Summary:
    busy_s: float                       # union of op intervals, per chip
    window_s: float
    n_devices: int                      # chips averaged over
    n_ops: int
    device_ops: List[Tuple[str, float]]  # top ops by summed device seconds
    idle_gaps: List[Tuple[str, float]]   # longest gaps, named by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """``%sort.6 = s32[..] sort(..)`` -> ``sort.6``;
    ``jit__sweep(1319..)`` -> ``jit__sweep``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except Exception:  # an event without stats
        return {}


def collect(profile, platform: str):
    """``(ops by device, bench spans)`` of a ``jax.profiler.ProfileData``."""
    ops: Dict[str, List[Op]] = defaultdict(list)
    spans: List[Span] = []
    for plane in profile.planes:
        device = plane.name.startswith("/device:") and platform != "cpu"
        modules: List[Tuple[float, float, str]] = []
        for line in plane.lines:
            if device and line.name == "XLA Modules":
                modules += [(float(ev.start_ns),
                             float(ev.start_ns) + float(ev.duration_ns),
                             short_name(ev.name)) for ev in line.events]
                continue
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                name = ev.name
                if not device and name.startswith(SPAN_PREFIX):
                    spans.append(Span(name, start, start + dur))
                    continue
                if device:
                    st = _stats(ev)
                    ops[plane.name].append(
                        Op(short_name(name), start, start + dur,
                           short_name(str(st.get("hlo_module", "")))))
                elif platform == "cpu" and plane.name.startswith("/host:"):
                    st = _stats(ev)
                    if "hlo_op" in st:
                        ops["/host:CPU"].append(
                            Op(name, start, start + dur,
                               str(st.get("hlo_module", ""))))
        if modules and plane.name in ops:
            _name_modules(ops[plane.name], sorted(modules))
    return dict(ops), spans


def _name_modules(ops: List[Op], modules: List[Tuple[float, float, str]]):
    """Give each op without an ``hlo_module`` stat the module it runs in."""
    starts = [m[0] for m in modules]
    for op in ops:
        if op.module:
            continue
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i >= 0 and op.start_ns < modules[i][1]:
            op.module = modules[i][2]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _innermost(spans: List[Span], t: float) -> str:
    best: Optional[Span] = None
    for sp in spans:
        if sp.start_ns <= t < sp.end_ns and (
                best is None or sp.end_ns - sp.start_ns
                < best.end_ns - best.start_ns):
            best = sp
    return best.name if best is not None else "outside spans"


def reduce(ops: Dict[str, List[Op]], spans: List[Span],
           window: Tuple[float, float], chips: int = 1) -> Summary:
    """Busy time and breakdown of the window ``(start_ns, end_ns)``, per
    chip of the ``chips`` the run used."""
    w0, w1 = window
    if not ops:
        raise ValueError("the trace holds no device operation")
    if len(ops) > chips:
        raise ValueError(f"the trace has operations on {len(ops)} devices, "
                         f"the run used {chips}")
    busy_total = 0.0
    per_op: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    n_ops = 0
    for _dev, dev_ops in sorted(ops.items()):
        inside = [o for o in dev_ops if o.end_ns > w0 and o.start_ns < w1]
        n_ops += len(inside)
        clipped = [(max(o.start_ns, w0), min(o.end_ns, w1)) for o in inside]
        merged = _union(clipped)
        busy_total += sum(e - s for s, e in merged)
        for o in inside:
            key = f"{o.module}/{o.name}" if o.module else o.name
            per_op[key] += min(o.end_ns, w1) - max(o.start_ns, w0)
        starts = sorted(inside, key=lambda o: o.start_ns)
        edges = [w0] + [e for _s, e in merged]
        nexts = [s for s, _e in merged] + [w1]
        j = 0
        for g0, g1 in zip(edges, nexts):
            if g1 <= g0:
                continue
            while j < len(starts) and starts[j].start_ns < g1:
                j += 1
            after = starts[j] if j < len(starts) else None
            then = (after.module or after.name) if after is not None \
                else "window end"
            gaps[f"{_innermost(spans, (g0 + g1) / 2)} before {then}"] += \
                g1 - g0
    # A chip with no operation in the trace idled through the window.
    if chips > len(ops):
        gaps[IDLE_CHIP] += (chips - len(ops)) * (w1 - w0)
    n_dev = chips
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        busy_s=busy_total / n_dev / 1e9,
        window_s=(w1 - w0) / 1e9,
        n_devices=n_dev,
        n_ops=n_ops,
        device_ops=[(k, v / n_dev / 1e9) for k, v in top_ops],
        idle_gaps=[(k, v / n_dev / 1e9) for k, v in top_gaps],
    )


def window_of(spans: List[Span], name: str) -> Tuple[float, float]:
    """Extent of the single span ``name``."""
    hits = [sp for sp in spans if sp.name == name]
    if len(hits) != 1:
        raise ValueError(f"expected one span {name!r}, found {len(hits)}")
    return hits[0].start_ns, hits[0].end_ns


def summarize(log_dir: str, platform: str, window_span: str,
              chips: int = 1) -> Summary:
    """Read the trace under ``log_dir`` and reduce the span ``window_span``
    over the run's ``chips``. The CPU backend's devices all run on the
    host's threads, which ``collect`` reads as one device."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(find_xplane(log_dir))
    ops, spans = collect(profile, platform)
    return reduce(ops, spans, window_of(spans, window_span),
                  1 if platform == "cpu" else chips)
