"""Serving cells: the program's ``ServingEngine`` under open-loop traffic.

The benchmark's own wall-clock loop submits each request when it is due,
admits queued requests onto free lanes (``admit``, which prefills them and
returns their first tokens) and runs decode steps (``step``). A request is
timed from when it was due, so a stall counts against every request that
waits behind it, and each of its tokens is stamped when the call that
produced it returned. Requests due in the window are the measured ones; the loop
goes on, with arrivals at the same rate, until each of them has finished,
or ``drain_s`` after the window has closed. A measured request that never
gets a token counts as slower than every one that did.

After the loop, a sample of finished requests drawn from the seed, the
longest among them, is run through the plain reference in float32: each
served token's logit must lie within the limit of the reference's best.
"""

from __future__ import annotations

import functools
import gc
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

import devtrace
import generator
import harness
import lm
from harness import Limit, Run


def run(r: Run, devices, arch_override=None, break_engine=None,
        stand_in: Optional[str] = None) -> None:
    """Fill ``r``. ``arch_override`` and ``break_engine`` are for the tests
    under ``bench/tests``: a small model on the CPU, and a fault planted in
    the timed path. With ``stand_in="fp8"`` (the control) the reference in
    float8 takes the program's place in the comparison: at each position
    of the served requests, the token it puts first is judged."""
    from repro.launch.serve import Request, ServingEngine

    c, tr = r.config, r.traffic
    key = harness.key_from_seed(r.seed)
    model = lm.program_model(c, arch_override)
    params = lm.seeded_params(c, model, key)
    engine = ServingEngine(model, params, max_batch=tr["max_batch"],
                           max_seq=tr["max_seq"])
    if break_engine is not None:
        break_engine(engine)
    r.note(max_batch=tr["max_batch"], max_seq=tr["max_seq"],
           prefill_chunk=engine.prefill_chunk,
           rehearsal=tr["max_batch_rehearsal"])
    mix = tr["mix"]
    todo = generator.arrivals(mix, tr["rate_per_s"], r.seed, r.seconds,
                              c["vocab_size"])

    # warm-up: every compiled shape the window uses (zero-lane, prefill
    # chunk, decode step), on a throwaway request
    warm = Request(id=-1, prompt=todo[0].prompt[:engine.prefill_chunk + 1],
                   max_new=3)
    engine.submit(warm)
    engine.admit()
    while engine.active.any():
        engine.step()
    engine.finished.clear()

    # -- the window ----------------------------------------------------------
    by_id: Dict[int, Request] = {}
    due_at: Dict[int, float] = {}
    late: List[float] = []
    pending = deque(todo)
    measured = {a.index for a in todo if a.in_window}
    r.open_window(start_trace=False)
    t0 = time.monotonic()       # the engine stamps tokens on this clock
    close, last = t0 + r.seconds, t0 + r.seconds + mix["drain_s"]
    # the traced slice: the window's last seconds, once the lanes have
    # filled; writing the trace out stalls the loop after the close, and
    # the drain is lengthened by that stall
    trace_from = close - min(tr["trace_seconds"], r.seconds)
    traced = [None, None]           # when the profiler ran, on this clock
    admit_s = prompt_tokens = 0.0
    steps: List[float] = []
    decode_log: List[tuple] = []     # (start, live lanes' positions)
    stamps: Dict[int, List[float]] = {}   # request -> its decoded tokens' times
    while True:
        t = time.monotonic()
        if r.trace and traced[0] is None and t >= trace_from:
            r.profiler.start()
            traced[0] = t = time.monotonic()
        if r.trace and traced[1] is None and t >= close:
            r.profiler.stop()
            traced[1] = t
            t = time.monotonic()
            last += t - traced[1]   # the drain waits out the trace's writing
        while pending and t0 + pending[0].due_s <= t:
            a = pending.popleft()
            req = Request(id=a.index, prompt=a.prompt, max_new=a.max_new)
            engine.submit(req)
            by_id[a.index], due_at[a.index] = req, t0 + a.due_s
            if a.in_window:
                late.append(t - due_at[a.index])
        if t >= last or (t >= close and all(
                by_id[i].done_time is not None for i in measured)):
            break
        if engine.queue and engine.free_lanes():
            a0 = time.monotonic()
            with devtrace.span("admit"):
                admitted = engine.admit()
            admit_s += time.monotonic() - a0
            prompt_tokens += sum(len(q.prompt) for q in admitted)
        if engine.active.any():
            lanes = np.nonzero(engine.active)[0]
            live = engine.positions[lanes].tolist()
            got = [engine.lane_req[lane].id for lane in lanes]
            s0 = time.monotonic()
            with devtrace.span("decode"):
                engine.step()
            s1 = time.monotonic()
            steps.append(s1 - s0)
            decode_log.append((s0, live))
            for i in got:              # each live lane got a token at s1
                stamps.setdefault(i, []).append(s1)
        elif not engine.queue and pending:
            time.sleep(max(0.0, min(0.002, t0 + pending[0].due_s - t)))
    r.t_window_end = r.t_window + r.seconds
    r.close_window()

    # -- what the measured requests saw --------------------------------------
    for i in sorted(measured):
        req = by_id[i]
        first, done = req.first_token_time, req.done_time
        r.add("ttft_s", (first - due_at[i]) if first is not None
              else float("inf"))
        times = ([first] if first is not None else []) + stamps.get(i, [])
        r.add("tpot_s", (times[-1] - times[0]) / (len(times) - 1)
              if done is not None and len(times) > 1 else float("inf"))
    finished = [by_id[i] for i in sorted(measured)
                if by_id[i].done_time is not None]
    r.attempted = len(measured)
    r.failed = len(measured) - len(finished)
    r.counters.update(prompt_tokens_admitted=prompt_tokens,
                      admit_seconds=admit_s, decode_steps=len(steps))
    if steps:
        r.samples["decode_step_s"] = steps
    # the decode steps the trace saw, for the per-step work
    if r.trace:
        on = traced[0]
        off = traced[1] if traced[1] is not None else float("inf")
        r.info["traced_decode_positions"] = [
            pos for s0, pos in decode_log if on is not None and on <= s0 < off]
    r.note(requests_due=len(measured), requests_completed=len(finished),
           requests_served_in_all=len(engine.finished),
           generator_late_p90_s=harness.percentile(late, 90) if late else 0,
           generator_late_max_s=max(late) if late else 0,
           decode_compiles=engine.compile_count,
           prefill_compiles=engine.prefill_compile_count)
    r.memory_peak_bytes = harness.memory_peak_bytes(devices)

    # -- the reference, once the program's state is freed ---------------------
    if not tr["check"]["requests"]:     # a knee sweep compares nothing
        return
    sample = _sample(finished, r.seed, tr["check"])
    served = [(np.asarray(q.prompt, np.int32), list(q.tokens))
              for q in sample]
    r.info["served"] = served
    del engine, params, by_id, finished, sample
    gc.collect()
    gap = max(token_gaps(c, key, served, tr["max_seq"], quant=stand_in))
    r.note(checked_requests=len(served),
           checked_tokens=sum(len(t) for _, t in served))
    r.limits.append(Limit("served_logit_gap", gap,
                          tr["limits"]["served_logit_gap"]))


def _sample(finished, seed: int, check: Dict) -> list:
    """The longest finished request and ``requests - 1`` more drawn from
    the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda q: len(q.prompt) + len(q.tokens))
    rest = [q for q in finished if q is not longest]
    rng = np.random.default_rng((seed, 1))
    k = min(len(rest), check["requests"] - 1)
    picks = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(picks)]


def token_gaps(c, key, served, max_seq: int, quant: Optional[str] = None
               ) -> List[float]:
    """Per request, the widest gap by which a served token's logit lies
    below the reference's best at its position. With ``quant``, the token
    read at each position is the one the reference at that precision puts
    first (the control), and the gap is still read from the float32
    reference."""
    import jax
    import jax.numpy as jnp

    if not served:
        return [float("inf")]
    ref = lm.reference_module(c)
    p = jax.jit(functools.partial(ref.make_params, c,
                                  dtype=jnp.bfloat16))(key)
    p = jax.tree.map(lambda x: x.astype(jnp.float32), p)

    @jax.jit
    def best_and_logits(p, tokens):
        lg = ref.logits(c, p, ref.hidden(c, p, tokens))
        return lg.max(-1), lg

    @jax.jit
    def low_argmax(p, tokens):
        return ref.logits(c, p, ref.hidden(c, p, tokens, quant),
                          quant).argmax(-1)

    out = []
    for prompt, tokens in served:
        seq = np.zeros((max_seq,), np.int32)
        full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
        seq[:len(full)] = full
        pos = np.arange(len(prompt) - 1, len(full))
        best, lg = best_and_logits(p, jnp.asarray(seq))
        pick = np.asarray(tokens, np.int64) if quant is None else np.asarray(
            low_argmax(p, jnp.asarray(seq)))[pos]
        got = np.asarray(lg[pos, np.minimum(pick, c["vocab_size"] - 1)])
        gap = np.asarray(best)[pos] - got
        if np.any(pick >= c["vocab_size"]):
            gap = np.full_like(gap, np.inf)
        out.append(float(gap.max()))
        del lg
    return out
