"""Reduction of a profiler trace to device busy time, the heaviest device
operations, the longest idle gaps and per-program device time.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds every operation the chip ran and whose ``XLA Modules`` line
holds every program execution (``jit_<name>(<fingerprint>)``). The
benchmark's own host spans are ``jax.profiler.TraceAnnotation``\\ s named
``bench.<what>`` on the host plane; the one named ``bench.window`` bounds
the traced window. Host and device clocks in the trace agree to about a
millisecond.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]          # (start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%").strip()


def read(path: str) -> Dict:
    """Raw events of a trace file: per device its ops and program runs,
    and the benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    d["ops"] = [(op_name(e.name), int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events]
                elif line.name == MODULES_LINE:
                    d["modules"] = [(e.name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns))
                                    for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def reduce(raw: Dict, top: int = 10) -> Dict:
    """Busy and window seconds averaged over the chips, the ``top`` device
    operations by time, the ``top`` longest idle gaps named by the host
    span they fall in, and device seconds of each program in each kind of
    host span."""
    windows = [(s, e) for n, s, e in raw["spans"] if n == "bench.window"]
    if not windows or not raw["devices"]:
        raise ValueError("trace has no bench.window span or no TPU plane")
    lo, hi = windows[0]
    busy, per_op, gaps = [], {}, []
    host = [s for s in raw["spans"] if s[0] != "bench.window"]
    for dev in raw["devices"].values():
        ops = [(n, s, e) for n, s, e in dev["ops"] if e > lo and s < hi]
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(total(merged))
        for n, s, e in ops:
            per_op[n] = per_op.get(n, 0) + min(e, hi) - max(s, lo)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for k in range(0, len(edges), 2):
            g0, g1 = edges[k], edges[k + 1]
            if g1 > g0:
                gaps.append((_span_at(host, (g0 + g1) // 2), g1 - g0))
    n = len(raw["devices"])
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(gaps, key=lambda g: -g[1])[:top]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_top],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps_top],
        "programs_in": programs_in_spans(raw, host),
    }


def _span_at(host: List[Tuple[str, int, int]], t: int) -> str:
    inside = [(e - s, n) for n, s, e in host if s <= t <= e]
    return min(inside)[1] if inside else "host, between bench spans"


def programs_in_spans(raw: Dict, host, slack_ns: int = 1_000_000
                      ) -> Dict[str, List[Dict[str, float]]]:
    """Device seconds of the programs each host span ran, one dict per
    span in the order of the spans: ``{span: [{program: seconds}, ...]}``.
    A program's seconds are those in which one of the device's operations
    ran while it executed.

    Each program execution belongs to exactly one span: the one with the
    latest start at or before the execution's start, if the execution
    starts before that span ends. ``slack_ns`` allows for the offset of
    the host and device clocks, and is much shorter than any span, so
    spans that run back to back never share an execution."""
    import bisect

    dev = next(iter(raw["devices"].values()))
    busy = union([(s, e) for _, s, e in dev["ops"]])
    order = sorted(range(len(host)), key=lambda k: host[k][1])
    starts = [host[k][1] for k in order]
    per: List[Dict[str, float]] = [{} for _ in host]
    for prog, s, e in dev["modules"]:
        j = bisect.bisect_right(starts, s + slack_ns) - 1
        if j < 0:
            continue
        k = order[j]
        if s > host[k][2] + slack_ns:
            continue                # between spans: no span's work
        per[k][prog] = per[k].get(prog, 0.0) + total(
            clip(busy, s, e)) / 1e9
    out: Dict[str, List[Dict[str, float]]] = {}
    for (name, _, _), progs in zip(host, per):
        out.setdefault(name, []).append(progs)
    return out


class Profiler:
    """The JAX profiler around a window, writing under ``$TMPDIR``."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.active = False
        self._window = None

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.log_dir)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.active = True

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def result(self) -> Dict:
        return reduce(read(find_xplane(self.log_dir)))


def span(name: str):
    """A host span in the trace (``bench.<name>``); costs next to nothing
    when no trace is being taken."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def device_seconds_per_span(reduced: Dict, span_name: str
                            ) -> Optional[List[float]]:
    """Device seconds of every program run inside each span named
    ``span_name``, one number per span, in order."""
    spans = reduced["programs_in"].get(SPAN_PREFIX + span_name)
    if not spans:
        return None
    return [sum(progs.values()) for progs in spans]
