"""The program's own host spans in a profiler trace, and the device's idle
time split over them.

The program marks the host side of each layer with a span
(``repro.launch.runtime.span``): a ``jax.profiler.TraceAnnotation`` named
``repro.<layer>[.<part>]``, whose integer ids (slot ``t``, ``step``,
``req``, ``lanes``, ...) are the event's stats, on the clock of the
device planes. ``bench/devtrace.py`` reads the benchmark's own ``bench.*``
spans only; this module reads both families and says, for every instant
in which the chip ran no operation, what the host was doing: the innermost
span open at that instant, or ``host, between spans``.

Device busy intervals come from ``devtrace.read``'s ``ops`` of one chip;
every function here takes them as ``(start_ns, end_ns)`` pairs.
"""

from __future__ import annotations

import bisect
import heapq
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import devtrace

Interval = Tuple[int, int]
Span = Tuple[str, int, int, Dict[str, int]]    # name, start, end, ids

PROGRAM_PREFIX = "repro."
WINDOW = "bench.window"
BETWEEN = "host, between spans"


def read(path: str) -> List[Span]:
    """Every host span of the trace named ``repro.*`` or ``bench.*``, with
    its ids, in the order of their starts."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((PROGRAM_PREFIX, devtrace.SPAN_PREFIX)):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def timeline(spans: Sequence[Span], lo: int, hi: int
             ) -> List[Tuple[int, int, str]]:
    """``[lo, hi)`` cut into consecutive pieces, each named by the
    innermost span open over all of it: of the spans open, the one that
    started last (the shorter on a tie). Where none is open the piece is
    ``host, between spans``; ``bench.window`` is the window, not work, and
    names nothing."""
    work = [(max(s, lo), min(e, hi), s, e, n) for n, s, e, _ in spans
            if n != WINDOW and e > lo and s < hi]
    cuts = sorted({lo, hi} | {w[0] for w in work} | {w[1] for w in work})
    by_start = sorted(range(len(work)), key=lambda k: work[k][0])
    open_: List[Tuple[int, int, int]] = []      # (-start, end, index)
    out: List[Tuple[int, int, str]] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and work[by_start[i]][0] <= a:
            k = by_start[i]
            heapq.heappush(open_, (-work[k][2], work[k][3], k))
            i += 1
        while open_ and work[open_[0][2]][1] <= a:
            heapq.heappop(open_)    # the latest-started span has ended
        name = work[open_[0][2]][4] if open_ else BETWEEN
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle(busy: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of ``[lo, hi)`` in which no busy interval runs."""
    merged = devtrace.union(devtrace.clip(list(busy), lo, hi))
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]


def _overlaps(a: List[Interval], b: Sequence[tuple]):
    """The overlaps of ``a`` with ``b``, both sorted and disjoint:
    ``(i, k, start, end)`` for each piece that ``a[i]`` shares with
    ``b[k]``, in order."""
    j = 0
    for i, (s, e) in enumerate(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            yield i, k, max(s, b[k][0]), min(e, b[k][1])
            k += 1


def idle_by_span(busy: Iterable[Interval], spans: Sequence[Span], lo: int,
                 hi: int) -> Dict[str, float]:
    """Seconds of ``[lo, hi)`` in which the device ran nothing, by the
    innermost host span open at the time (``timeline``). The values sum
    to the window's idle time, window less busy, exactly."""
    pieces = timeline(spans, lo, hi)
    out: Dict[str, int] = {}
    for _, k, s, e in _overlaps(idle(busy, lo, hi), pieces):
        out[pieces[k][2]] = out.get(pieces[k][2], 0) + e - s
    return {k: v / 1e9
            for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def idle_gaps(busy: Iterable[Interval], spans: Sequence[Span], lo: int,
              hi: int, top: int = 10) -> List[List]:
    """The ``top`` longest idle gaps, ``[name, seconds]``, each named by
    the innermost span that holds the most of it."""
    pieces = timeline(spans, lo, hi)
    gaps = idle(busy, lo, hi)
    held: List[Dict[str, int]] = [{} for _ in gaps]
    for i, k, s, e in _overlaps(gaps, pieces):
        held[i][pieces[k][2]] = held[i].get(pieces[k][2], 0) + e - s
    named = [[max(h, key=h.get), (e - s) / 1e9]
             for h, (s, e) in zip(held, gaps)]
    return sorted(named, key=lambda g: -g[1])[:top]


def idle_under(busy: Iterable[Interval], spans: Sequence[Span], lo: int,
               hi: int, names: Sequence[str],
               outside: Sequence[str] = ()) -> Optional[float]:
    """Idle seconds of ``[lo, hi)`` while a span named in ``names`` is open
    and none named in ``outside`` is; ``None`` when no span in the window
    is named in ``names``."""
    def covered(which):
        return devtrace.union(devtrace.clip(
            [(s, e) for n, s, e, _ in spans if n in which], lo, hi))

    under = covered(names)
    if not under:
        return None
    away = covered(outside)
    under = [iv for s, e in under for iv in idle(away, s, e)]
    both = _overlaps(idle(busy, lo, hi), under)
    return sum(e - s for _, _, s, e in both) / 1e9


def idle_shares(busy: Iterable[Interval], spans: Sequence[Span], lo: int,
                hi: int) -> Dict[str, float]:
    """The window's idle time under each layer's host code, in percent of
    the window; a share whose spans the trace lacks is left out.

    ``idle_share.train.step``: under ``repro.train.step`` (batch, dispatch,
    loss read-back). ``idle_share.train.slot_edge``: under ``repro.slot``
    but not a train step (the decision, ring forming, checkpoints,
    calibration, accounting). ``idle_share.serve.step``: under
    ``repro.serve.step`` (the decode step's dispatch, token read-back and
    lane loop)."""
    busy = list(busy)
    under_what = {
        "idle_share.train.step": (("repro.train.step",), ()),
        "idle_share.train.slot_edge": (("repro.slot",),
                                       ("repro.train.step",)),
        "idle_share.serve.step": (("repro.serve.step",), ()),
    }
    out = {}
    for metric, (names, outside) in under_what.items():
        v = idle_under(busy, spans, lo, hi, names, outside)
        if v is not None:
            out[metric] = 100.0 * v / ((hi - lo) / 1e9)
    return out


def dispatch_offsets(spans: Sequence[Span], modules: Sequence[tuple],
                     name: str) -> List[int]:
    """Per span named ``name``, the nanoseconds from its end to the start
    of the program execution (``devtrace.read``'s ``modules``) that starts
    nearest to that end: negative where the program began before the host
    span closed. Right where programs start further apart than twice the
    offset, as a decode or train step's do."""
    starts = sorted(s for _, s, _ in modules)
    out = []
    for n, _, e, _ in spans:
        if n != name or not starts:
            continue
        k = bisect.bisect_left(starts, e)
        near = [starts[j] for j in (k - 1, k) if 0 <= j < len(starts)]
        out.append(min(near, key=lambda x: abs(x - e)) - e)
    return out
