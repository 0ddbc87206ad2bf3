"""What every cell shares: the run record, the window, compile counting,
statistics, the device check and the result line.

A driver (``bench/drivers/<kind>.py``) fills a :class:`Run` with raw
measurements: host-clock samples, counters and, in a traced run, the
reduced device trace. The metric readers (``bench/metrics/<name>.py``)
turn those into the numbers that ``BENCHMARK.json`` names; a reader that
finds nothing to read returns ``None`` and the metric is left out.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class StopWindow(Exception):
    """Raised from inside the timed path when the window has closed."""


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileInWindow(RuntimeError):
    """Something traced or compiled while the window was open."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Limit:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """Everything one run of one cell measured."""

    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_process: float                       # perf_counter at process start
    t_window: Optional[float] = None       # first measured moment
    t_window_end: Optional[float] = None
    attempted: int = 0
    failed: int = 0
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    limits: List[Limit] = dataclasses.field(default_factory=list)
    device_trace: Optional[Dict[str, Any]] = None
    memory_peak_bytes: Optional[int] = None
    counter: Optional["CompileCounter"] = None
    profiler: Any = None                   # trace.Profiler in a traced run
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def open_window(self, start_trace: bool = True) -> None:
        """Everything before this moment is set-up. A driver that traces a
        slice of the window starts the profiler itself."""
        if self.trace and start_trace:
            self.profiler.start()
        if self.counter is not None:
            self.counter.open_window()
        self.t_window = now()

    def close_window(self) -> None:
        """After the last measured moment (``t_window_end``, or now)."""
        if self.t_window_end is None:
            self.t_window_end = now()
        if self.trace:
            self.profiler.stop()
        n = self.counter.in_window() if self.counter is not None else 0
        self.counters["compile_events_in_window"] = n
        if n:
            raise CompileInWindow(f"{n} trace/compile events inside the "
                                  "window: a shape was not warmed up")

    @property
    def window_s(self) -> float:
        return self.t_window_end - self.t_window

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_process

    def add(self, name: str, value: float) -> None:
        self.samples.setdefault(name, []).append(float(value))

    def note(self, **kw) -> None:
        """An earlier line of the run's output: read by people, not the
        driver."""
        self.info.update(kw)
        print(json.dumps(kw, default=float), flush=True)


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of the samples at or below it (an observed value, never an
    interpolation)."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


# ---------------------------------------------------------------------------
# compile counting
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts JAX's tracing, lowering and compiling, and its cache hits.

    Any ``/jax/core/compile/`` event means a function met a shape it had
    not met before; inside the window there must be none.
    """

    def __init__(self):
        self.events = 0
        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._window_start_events: Optional[int] = None

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.events += 1
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.backend_compiles += 1

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def open_window(self) -> None:
        self._window_start_events = self.events

    def in_window(self) -> int:
        if self._window_start_events is None:
            return 0
        return self.events - self._window_start_events

    def summary(self) -> Dict[str, float]:
        return {"compile_events": self.events,
                "compile_seconds": self.seconds,
                "backend_compiles": self.backend_compiles,
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def require_chips(n: int):
    """The devices of the run; raises :class:`NoChip` without a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")
    return devices[:n]


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in bench/peaks.json")
    return table["devices"][device_kind]


def memory_peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


def key_from_seed(seed: int):
    """A PRNG key from any seed up to 64 bits (the driver's exceed 32)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def enable_compile_cache() -> str:
    """The program's persistent compile cache, kept in the checkout (or in
    ``$JAX_COMPILATION_CACHE_DIR``), with every program cached so that a
    second run compiles nothing."""
    import jax

    from repro.launch.runtime import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def now() -> float:
    return time.perf_counter()


def emit_limits(limits: List[Limit]) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for lim in limits:
        print(f"{lim.name} {lim.value!r} limit {lim.limit!r} "
              f"{'ok' if lim.ok else 'FAILED'}", file=sys.stderr, flush=True)


def result_line(run: Run, metrics: Dict[str, Tuple[float, str]],
                device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]]) -> str:
    out: Dict[str, Any] = {
        "correct": bool(run.limits) and all(l.ok for l in run.limits),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {l.name: {"value": l.value, "limit": l.limit}
                       for l in run.limits}
    return json.dumps(out)
