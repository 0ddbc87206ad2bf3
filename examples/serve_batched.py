"""Continuous-batching serving across architecture families + co-scheduling.

Two demos:

  1. **Engine** — a :class:`~repro.launch.serve.ServingEngine` per family
     (KV cache for attention archs, ring-buffer KV for SWA, recurrent state
     for Mamba2/RWKV6) serving a staggered burst of requests through one
     compiled decode step: requests admit onto free cache lanes mid-run,
     retire on EOS/max_new without draining the batch, and the engine ends
     the run with ``decode_compiles == 1`` whatever the batch composition
     looked like.
  2. **Co-scheduling** — the same engine driven *by the GADGET scheduler*
     (resolved through ``repro.sched.registry``): a training job and a
     ``ServeJob`` share a scarce 4-GPU cluster, a scripted diurnal burst of
     inference requests lands mid-run, and the slot-by-slot worker split
     shows the serving burst reclaiming workers from the training ring
     through the utility/Eq. (1) pricing — then handing them back once the
     backlog clears.

Reduced configs; runs on CPU in under a minute.

Usage:  PYTHONPATH=src python examples/serve_batched.py
"""

import time

import jax
import numpy as np

from repro.cluster.topology import Link, Server, SubstrateGraph
from repro.configs import get_arch
from repro.core.problem import DDLJSInstance, Job
from repro.core.utility import sqrt_utility
from repro.launch.runtime import enable_compile_cache
from repro.launch.serve import (
    Request,
    ServingEngine,
    audit_serving_engine,
    serve_requests,
)
from repro.models.model import build_model
from repro.sched import (
    DiurnalRequestStream,
    EmbeddingCommitted,
    OnlineDriver,
    RequestStreamConfig,
    ServeSLO,
    ServingBackend,
    make_serve_job,
    slo_attainment_from_events,
)

ARCHS = ["qwen3-0.6b", "h2o-danube-1.8b", "zamba2-1.2b", "rwkv6-7b"]


def engine_demo() -> None:
    print("== continuous batching per family "
          "(6 staggered requests, 3 lanes) ==")
    for arch in ARCHS:
        cfg = get_arch(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        engine = ServingEngine(model, params, max_batch=3, max_seq=32,
                               prefill_chunk=4)
        rng = np.random.default_rng(5)
        reqs = [Request(id=i,
                        prompt=rng.integers(0, cfg.vocab, size=6,
                                            dtype=np.int32),
                        max_new=8, arrival=4 * i)
                for i in range(6)]
        t0 = time.time()
        serve_requests(engine, reqs)
        dt = time.time() - t0
        problems = audit_serving_engine(engine)
        assert not problems, problems
        toks = sum(len(r.tokens) for r in engine.finished)
        cache_kind = {
            "dense": "ring-buffer KV" if cfg.sliding_window else "KV",
            "hybrid": "SSM state + shared-attn KV",
            "rwkv": "WKV state",
        }.get(cfg.family, "KV")
        print(f"{arch:18s} cache={cache_kind:24s} {toks / dt:7.1f} tok/s  "
              f"decode_compiles={engine.compile_count}  "
              f"served={len(engine.finished)}/6")


def coschedule_demo() -> None:
    print("\n== GADGET co-scheduling: burst reclaims workers from training ==")
    servers = [Server(i, 0, {"gpus": 2.0, "mem": 8.0}) for i in range(2)]
    links = []
    for s in servers:
        links += [Link(s.node, "r0", 100.0), Link("r0", s.node, 100.0)]
    graph = SubstrateGraph(servers, links, n_racks=1, n_core=0)
    horizon, burst_start = 16, 6

    train = Job(id=0, arrival=0, max_workers=4,
                demands={"gpus": 1.0, "mem": 1.0}, budgets={"gpus": 500.0},
                bandwidth=5.0, zeta=1.0, utility=sqrt_utility(4.0))
    slo = ServeSLO(ttft_slots=2, tpot_slots=1.0, weight=80.0)
    serve = make_serve_job(1, arrival=burst_start, offered_tokens=800.0,
                           slo=slo, tokens_per_worker_slot=64.0,
                           max_workers=3, bandwidth=5.0)
    inst = DDLJSInstance(graph=graph, jobs=[train, serve], horizon=horizon)

    cfg = get_arch("qwen3-0.6b").reduced()
    model = build_model(cfg)
    engine = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                           max_batch=4, max_seq=32, prefill_chunk=4)
    stream = DiurnalRequestStream(RequestStreamConfig(
        job_id=1, start=burst_start, base_rate=2.0, burst_prob=0.6,
        burst_size=4, prompt_len=(4, 8), max_new=(3, 6), seed=7))
    backend = ServingBackend({1: engine}, tokens_per_worker_slot=64.0)

    # scheduler resolved by name through the registry, like any other run
    res = OnlineDriver(inst, events=stream, backend=backend).run("gadget")

    workers = {0: dict.fromkeys(range(horizon), 0),
               1: dict.fromkeys(range(horizon), 0)}
    for e in res.events:
        if isinstance(e, EmbeddingCommitted):
            workers[e.job_id][e.t] += e.n_workers
    served = {r["t"]: r["served_tokens"] for r in backend.reports
              if "served_tokens" in r}
    print("slot  train  serve  served_tokens")
    for t in range(horizon):
        marker = "  <- burst starts" if t == burst_start else ""
        print(f"{t:4d}  {workers[0][t]:5d}  {workers[1][t]:5d}  "
              f"{served.get(t, 0):13d}{marker}")
    print(f"SLO attainment (from event log): "
          f"{slo_attainment_from_events(res.events, 1, slo):.3f}   "
          f"decode_compiles={engine.compile_count}")


def main() -> None:
    enable_compile_cache()
    engine_demo()
    coschedule_demo()


if __name__ == "__main__":
    main()
