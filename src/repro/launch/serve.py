"""Continuous-batching serving engine: fixed-shape decode over cache lanes.

The engine half of the PR 10 serving stack (the scheduler half lives in
:mod:`repro.sched.serving`). Three ideas, all standard in production LLM
servers (vLLM/Orca-style), mapped onto this repo's cache/model contracts:

  * **One compiled decode step, every batch composition.** The decode step
    is ``jax.jit``-compiled once over a fixed ``(max_batch, 1)`` token block
    with per-lane positions and an activity mask — admitting or retiring a
    request changes *data*, never *shapes*, so the XLA executable is reused
    for every occupancy from 1 lane to ``max_batch`` lanes.
    ``ServingEngine.compile_count`` counts traces the same way
    ``RingWorkerGroup.compile_count`` does (a Python side effect inside the
    traced function), and :func:`audit_serving_engine` is the runtime audit
    mirroring ``audit_compiled_step_cache``.
  * **Chunked prefill.** A prompt of length P costs ``ceil(P/chunk)``
    compiled calls (an internal ``lax.scan`` feeds ``chunk`` tokens through
    the family's ``decode_step`` per call) instead of the retired
    token-by-token loop's P calls — on CPU/host-dispatch-bound setups the
    per-call overhead dominates, so prefill throughput scales with the
    chunk. The padded tail of the final chunk is masked out of both cache
    and logits, which keeps generation token-identical to the old loop
    (pinned in tests/test_serving.py).
  * **Per-request cache lanes.** ``model.cache_specs(max_batch, max_seq)``
    allocates ``max_batch`` lanes once; requests are admitted onto free
    lanes mid-run (prefill interleaves with decode — no drain), retired on
    EOS/max-tokens, and an evicted lane is zeroed before reuse
    (:func:`repro.models.model.zero_cache_lane` — recurrent SSM/WKV state
    is not self-masking the way attention caches are).

``greedy_generate`` keeps its old signature but now prefills in chunks;
``greedy_generate_reference`` is the retired token-by-token loop, kept as
the regression oracle.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from collections import deque
from typing import Callable, Deque, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, list_archs
from repro.launch.runtime import device_summary, enable_compile_cache
from repro.models.model import (
    build_model,
    cache_lane,
    set_cache_lane,
    zero_cache_lane,
)
from repro.models.module import init_from_specs
from repro.training.train_step import make_serve_step

__all__ = [
    "Request",
    "ServingEngine",
    "audit_serving_engine",
    "greedy_generate",
    "greedy_generate_reference",
    "make_prefill_step",
    "serve_requests",
]


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

def make_prefill_step(model) -> Callable:
    """(params, cache, tokens(B,C), pos0, n_total) -> (cache, last(B,Vp)).

    One compiled call advances the whole batch through ``C`` prompt tokens:
    a ``lax.scan`` feeds ``tokens[:, i]`` at position ``pos0 + i`` through
    the family's own ``decode_step``. Steps with ``pos0 + i >= n_total``
    (the zero-padded tail of a prompt's final chunk) are masked out of the
    cache update and the returned logits, so ``last`` is always the logits
    of the *last real* prompt token — the argmax seed of generation.
    """

    def step(params, cache, tokens, pos0, n_total):
        def body(carry, i):
            cache, last = carry
            tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)
            logits, new_cache = model.decode_step(params, cache, tok,
                                                  pos0 + i)
            valid = (pos0 + i) < n_total
            cache = jax.tree.map(
                lambda n, o: jnp.where(valid, n, o).astype(o.dtype),
                new_cache, cache)
            last = jnp.where(valid, logits[:, -1, :], last)
            return (cache, last), None

        last0 = jnp.zeros((tokens.shape[0], model.cfg.padded_vocab),
                          jnp.float32)
        (cache, last), _ = jax.lax.scan(
            body, (cache, last0), jnp.arange(tokens.shape[1]))
        return cache, last

    return step


def greedy_generate(model, params, prompts: jnp.ndarray, max_new: int,
                    max_seq: int, *, prefill_chunk: int = 8):
    """Chunked prefill then greedy decode (token-identical to the retired
    token-by-token loop, at ``ceil(P/chunk)`` prefill calls instead of P)."""
    b, prompt_len = prompts.shape
    cache = model.steady_decode_cache(
        params, init_from_specs(model.cache_specs(b, max_seq),
                                jax.random.PRNGKey(0)))
    prefill = jax.jit(make_prefill_step(model))
    step = jax.jit(make_serve_step(model))
    c = max(1, int(prefill_chunk))
    n_total = jnp.int32(prompt_len)
    last = None
    for c0 in range(0, prompt_len, c):
        chunk = prompts[:, c0:c0 + c]
        if chunk.shape[1] < c:
            chunk = jnp.pad(chunk, ((0, 0), (0, c - chunk.shape[1])))
        cache, last = prefill(params, cache, chunk, jnp.int32(c0), n_total)
    if max_new <= 0:
        return prompts
    tok = jnp.argmax(last[:, None, :], axis=-1).astype(jnp.int32)
    out = jnp.concatenate([prompts, tok], axis=1)
    for t in range(prompt_len, prompt_len + max_new - 1):
        logits, cache = step(params, cache, tok, jnp.int32(t))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out = jnp.concatenate([out, tok], axis=1)
    return out


def greedy_generate_reference(model, params, prompts: jnp.ndarray,
                              max_new: int, max_seq: int):
    """The retired token-by-token loop (one compiled call *per prompt
    token*) — kept verbatim as the regression oracle for the chunked path."""
    b, prompt_len = prompts.shape
    cache = init_from_specs(model.cache_specs(b, max_seq),
                            jax.random.PRNGKey(0))
    step = jax.jit(make_serve_step(model))
    tok = prompts[:, :1]
    logits = None
    for t in range(prompt_len + max_new - 1):
        logits, cache = step(params, cache, tok, jnp.int32(t))
        if t + 1 < prompt_len:
            tok = prompts[:, t + 1:t + 2]
        else:
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            prompts = jnp.concatenate([prompts, tok], axis=1)
    return prompts


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One generation request plus its lifecycle stamps.

    ``arrival`` is in engine-clock units (compiled calls — see
    :attr:`ServingEngine.clock`); :func:`serve_requests` holds a request
    back until the clock reaches it, which is how bursty arrival traces are
    replayed at the engine level. The ``*_clock`` stamps are filled by the
    engine (TTFT = ``first_token_clock - arrival``, in clock ticks); the
    ``*_time`` stamps are wall seconds for throughput reporting only —
    nothing decision-making reads them.
    """

    id: int
    prompt: np.ndarray
    max_new: int
    eos_token: Optional[int] = None
    arrival: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    truncated: bool = False
    submit_clock: Optional[int] = None
    first_token_clock: Optional[int] = None
    done_clock: Optional[int] = None
    submit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    done_time: Optional[float] = None

    @property
    def ttft_clock(self) -> Optional[int]:
        if self.first_token_clock is None:
            return None
        return self.first_token_clock - self.arrival

    @property
    def tpot_clock(self) -> Optional[float]:
        """Mean clock ticks per generated token after the first."""
        if self.done_clock is None or len(self.tokens) < 2:
            return None
        return ((self.done_clock - self.first_token_clock)
                / (len(self.tokens) - 1))


class ServingEngine:
    """Slot-based continuous batching over ``max_batch`` cache lanes.

    The decode step is compiled exactly once (fixed ``(max_batch, 1)``
    shapes; free lanes masked); prefill is compiled once per engine (fixed
    ``(1, prefill_chunk)`` shapes, lane index and positions are traced
    arguments). ``compile_count`` / ``prefill_compile_count`` /
    ``aux_compile_count`` count traces via trace-time side effects, and
    ``STATIC_CLOSURE_ATTRS`` + :meth:`closure_fingerprint` mirror the
    ``RingWorkerGroup`` recompile-hazard machinery — audited at runtime by
    :func:`audit_serving_engine`.
    """

    # attrs closed over by the compiled steps: mutating any of them after
    # construction would silently desynchronize the cached executables
    STATIC_CLOSURE_ATTRS = ("arch", "max_batch", "max_seq", "prefill_chunk")

    def __init__(self, model, params, *, max_batch: int, max_seq: int,
                 prefill_chunk: int = 8):
        self.model = model
        self.params = params
        self.arch = model.cfg.name
        self.max_batch = int(max_batch)
        self.max_seq = int(max_seq)
        self.prefill_chunk = max(1, int(prefill_chunk))
        # cast once to decode_step's dtype fixed point: the fixed-shape
        # compiled step must not round recurrent state back to the spec
        # dtype every token (see BaseModel.steady_decode_cache)
        self.cache = model.steady_decode_cache(
            params, init_from_specs(model.cache_specs(self.max_batch,
                                                      self.max_seq),
                                    jax.random.PRNGKey(0)))
        self.positions = np.zeros((self.max_batch,), np.int32)
        self.last_token = np.zeros((self.max_batch,), np.int32)
        self.active = np.zeros((self.max_batch,), bool)
        self.lane_req: List[Optional[Request]] = [None] * self.max_batch
        self.queue: Deque[Request] = deque()
        self.finished: List[Request] = []
        self.clock = 0          # compiled decode/prefill calls so far
        self.decode_steps = 0
        self.compile_count = 0          # decode-step traces (pinned == 1)
        self.prefill_compile_count = 0
        self.aux_compile_count = 0      # zero-lane traces
        self._closure_fingerprint = self.closure_fingerprint()
        self._decode = jax.jit(self._make_decode())
        self._prefill = jax.jit(self._make_prefill())
        self._zero = jax.jit(self._make_zero_lane())

    def closure_fingerprint(self) -> tuple:
        return tuple(getattr(self, a) for a in self.STATIC_CLOSURE_ATTRS)

    # -- compiled steps ------------------------------------------------------
    def _make_decode(self):
        model = self.model

        def step(params, cache, tokens, positions, active):
            # trace-time side effect: runs once per compile, not per call —
            # the same counting idiom as RingWorkerGroup.compile_count
            self.compile_count += 1
            logits, new_cache = model.decode_step_lanes(params, cache,
                                                        tokens, positions)
            def keep(n, o):
                mask = active.reshape((1, -1) + (1,) * (n.ndim - 2))
                return jnp.where(mask, n, o).astype(o.dtype)
            # free lanes are *masked*, not resized: their garbage decode
            # never lands in the cache, and the shapes never change
            new_cache = jax.tree.map(keep, new_cache, cache)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return nxt, new_cache

        return step

    def _make_prefill(self):
        chunk_step = make_prefill_step(self.model)

        def step(params, cache, lane, tokens, pos0, n_total):
            self.prefill_compile_count += 1
            one = cache_lane(cache, lane)
            one, last = chunk_step(params, one, tokens, pos0, n_total)
            return set_cache_lane(cache, one, lane), last[0]

        return step

    def _make_zero_lane(self):
        def step(cache, lane):
            self.aux_compile_count += 1
            return zero_cache_lane(cache, lane)

        return step

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) >= self.max_seq:
            raise ValueError(
                f"request {req.id}: prompt of {len(req.prompt)} tokens "
                f"cannot fit a max_seq={self.max_seq} cache lane")
        req.submit_clock = self.clock
        req.submit_time = time.monotonic()
        self.queue.append(req)

    def free_lanes(self) -> int:
        return int(self.max_batch - self.active.sum())

    def admit(self, limit: Optional[int] = None) -> List[Request]:
        """Prefill queued requests onto free lanes (no drain: the running
        batch keeps its cache, new lanes join at the next decode step).
        ``limit`` caps admissions (for callers metering prefill work, e.g.
        a backend spending a slot's token budget); default: fill all lanes.
        """
        admitted: List[Request] = []
        while self.queue and not self.active.all():
            if limit is not None and len(admitted) >= limit:
                break
            lane = int(np.argmin(self.active))
            req = self.queue.popleft()
            # evict barrier: the lane may hold a retired request's
            # recurrent state — zero it before the new prompt conditions
            # on it (attention caches are self-masking, SSM/WKV state is not)
            self.cache = self._zero(self.cache, jnp.int32(lane))
            prompt = np.asarray(req.prompt, np.int32)
            c = self.prefill_chunk
            n_total = jnp.int32(len(prompt))
            last = None
            for c0 in range(0, len(prompt), c):
                chunk = prompt[c0:c0 + c]
                if len(chunk) < c:
                    chunk = np.pad(chunk, (0, c - len(chunk)))
                self.cache, last = self._prefill(
                    self.params, self.cache, jnp.int32(lane),
                    jnp.asarray(chunk[None, :]), jnp.int32(c0), n_total)
                self.clock += 1
            tok = int(np.argmax(np.asarray(last)))
            req.tokens.append(tok)
            req.first_token_clock = self.clock
            req.first_token_time = time.monotonic()
            if self._is_done(req, tok, len(prompt)):
                self._retire(req)
            else:
                self.lane_req[lane] = req
                self.positions[lane] = len(prompt)
                self.last_token[lane] = tok
                self.active[lane] = True
            admitted.append(req)
        return admitted

    def step(self) -> List[Request]:
        """One fixed-shape decode step over every lane; returns the requests
        that finished (EOS / max_new / cache-full) this step."""
        if not self.active.any():
            return []
        nxt, self.cache = self._decode(
            self.params, self.cache,
            jnp.asarray(self.last_token.reshape(-1, 1)),
            jnp.asarray(self.positions), jnp.asarray(self.active))
        nxt = np.asarray(nxt)
        self.clock += 1
        self.decode_steps += 1
        done: List[Request] = []
        for lane in np.nonzero(self.active)[0]:
            req = self.lane_req[lane]
            tok = int(nxt[lane])
            req.tokens.append(tok)
            self.positions[lane] += 1
            self.last_token[lane] = tok
            if self._is_done(req, tok, int(self.positions[lane])):
                self.active[lane] = False
                self.lane_req[lane] = None
                self._retire(req)
                done.append(req)
        return done

    def _is_done(self, req: Request, tok: int, position: int) -> bool:
        if req.eos_token is not None and tok == req.eos_token:
            return True
        if len(req.tokens) >= req.max_new:
            return True
        if position >= self.max_seq:  # lane cache full: truncate
            req.truncated = True
            return True
        return False

    def _retire(self, req: Request) -> None:
        req.done_clock = self.clock
        req.done_time = time.monotonic()
        self.finished.append(req)

    @property
    def idle(self) -> bool:
        return not self.queue and not self.active.any()


def serve_requests(engine: ServingEngine, requests: Sequence[Request], *,
                   static: bool = False, max_steps: Optional[int] = None,
                   ) -> List[Request]:
    """Drive an engine over an arrival trace until every request finishes.

    ``static=True`` is the classic static-batching baseline: a new batch is
    admitted only once *every* lane has drained, so the batch runs at the
    pace of its longest request (the continuous path refills lanes the step
    they free up). Arrivals are in engine-clock units; when nothing is
    runnable yet the clock idles forward to the next arrival.
    """
    pending: Deque[Request] = deque(
        sorted(requests, key=lambda r: (r.arrival, r.id)))
    steps = 0
    while pending or engine.queue or engine.active.any():
        while pending and pending[0].arrival <= engine.clock:
            engine.submit(pending.popleft())
        if not static or not engine.active.any():
            engine.admit()
        if engine.active.any():
            engine.step()
        elif pending:
            engine.clock += 1  # idle tick: wait for the next arrival
        steps += 1
        if max_steps is not None and steps >= max_steps:
            break
    return engine.finished


def audit_serving_engine(engine: ServingEngine) -> List[str]:
    """Runtime audit of the engine's compiled-step + lane invariants
    (the serving analogue of ``audit_compiled_step_cache``). Returns
    problem strings (empty = clean); read-only.

      * the fixed-shape decode step compiled at most once, and exactly once
        if any decode step ran — varying batch occupancy must not re-trace;
      * prefill/zero-lane steps likewise compiled at most once each (lane
        index, positions and valid-lengths are traced, not static);
      * the closed-over static attrs still match the construction-time
        fingerprint;
      * lane-table invariants: a request occupies at most one lane (no
        aliasing), every active lane has a request and an in-bounds
        position, every inactive lane is empty.
    """
    problems: List[str] = []
    if engine.decode_steps > 0 and engine.compile_count != 1:
        problems.append(
            f"decode step ran {engine.decode_steps}x but compiled "
            f"{engine.compile_count}x — the (max_batch, 1) shape contract "
            "is broken (occupancy must be data, not shape)")
    if engine.decode_steps == 0 and engine.compile_count > 1:
        problems.append(
            f"decode step compiled {engine.compile_count}x without running")
    if engine.prefill_compile_count > 1:
        problems.append(
            f"prefill chunk step compiled {engine.prefill_compile_count}x "
            "— lane/position/valid-length must be traced arguments")
    if engine.aux_compile_count > 1:
        problems.append(
            f"zero-lane step compiled {engine.aux_compile_count}x")
    fp = engine.closure_fingerprint()
    if fp != engine._closure_fingerprint:
        problems.append(
            f"closed-over static attrs {engine.STATIC_CLOSURE_ATTRS} "
            f"changed after construction ({engine._closure_fingerprint!r} "
            f"-> {fp!r}) — the compiled steps are stale")
    seen = {}
    for lane, req in enumerate(engine.lane_req):
        if engine.active[lane]:
            if req is None:
                problems.append(f"active lane {lane} has no request")
                continue
            if id(req) in seen:
                problems.append(
                    f"request {req.id} aliased to lanes "
                    f"{seen[id(req)]} and {lane}")
            seen[id(req)] = lane
            if not 0 < engine.positions[lane] <= engine.max_seq:
                problems.append(
                    f"lane {lane} position {engine.positions[lane]} "
                    f"outside (0, {engine.max_seq}]")
        elif req is not None:
            problems.append(
                f"inactive lane {lane} still holds request {req.id} — "
                "evict must clear the lane table")
    return problems


# ---------------------------------------------------------------------------
# CLI demo
# ---------------------------------------------------------------------------

def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=list_archs())
    p.add_argument("--reduced", action="store_true",
                   help="serve the reduced (CPU-sized) config in float32 "
                        "(default: published widths, bf16 params)")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--chunk", type=int, default=8)
    args = p.parse_args()

    enable_compile_cache()
    print(json.dumps({"device": device_summary()}), flush=True)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        dtype=jnp.float32 if args.reduced else jnp.bfloat16)
    rng = np.random.default_rng(1)
    engine = ServingEngine(model, params, max_batch=args.batch,
                           max_seq=args.prompt_len + args.max_new,
                           prefill_chunk=args.chunk)
    reqs = [Request(id=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new=args.max_new)
            for i in range(args.batch)]
    t0 = time.time()
    done = serve_requests(engine, reqs)
    dt = time.time() - t0
    toks = sum(len(r.tokens) for r in done)
    assert not audit_serving_engine(engine)
    print(json.dumps({
        "arch": cfg.name,
        "requests": len(done),
        "tokens_per_s": round(toks / dt, 2),
        "decode_compiles": engine.compile_count,
        "sample": list(reqs[0].prompt) + reqs[0].tokens,
    }, default=int))


if __name__ == "__main__":
    main()
