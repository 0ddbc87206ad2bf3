"""Process start-up shared by the entry points: compile cache, device line,
and the host spans the program marks its work with.

Every entry point (``launch/train.py``, ``launch/serve.py``, the
``examples/`` drivers and ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile, and prints
:func:`device_summary` so its output names the device it ran on.
"""

from __future__ import annotations

import os
from typing import Dict

from jax.profiler import TraceAnnotation

# <repo>/.jax_cache: a fixed path, because the cache key includes it — a
# directory that moved between runs would never hit
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_summary() -> Dict[str, object]:
    """``platform``, ``kind`` and ``count`` of the devices JAX landed on."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def span(name: str, **ids) -> TraceAnnotation:
    """A host span ``repro.<name>`` in the profiler's trace, on the clock of
    the device planes; ``ids`` (integers) become the event's stats.

    Spans mark host code only, never the inside of a jitted function, and
    nest as the calls that open them do. With no profiler running a span
    is inert and costs about a microsecond.
    """
    return TraceAnnotation("repro." + name, **ids)
