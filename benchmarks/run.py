"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Figures:

  fig4  total utility vs number of jobs: GADGET vs FIFO / DRF / LAS
  fig5  embedded ratio vs node (GPU) capacity
  fig6  embedded ratio vs edge (bandwidth) capacity
  fig7  G-VNE approximation ratio vs exact branch-and-bound (HiGHS)
  fig8  contention sweep: utility + fair-share slowdown vs oversubscription
  eq1   RAR iteration-time model table (paper §III-3)
  re_ring  mid-slot re-ring (elastic reshard) cost vs the paper's
           checkpoint-preemption model (spawns 8 virtual CPU devices)
  compress  compressed-ring microbench: f32 ring vs XLA int8 ring vs the
            fused single-ppermute Pallas ring, plus the bf16/fp8 wire
            formats and the bucketed overlap pipeline (exposed-comm +
            hidden-fraction rows; spawns 8 virtual CPU devices;
            wire-bytes + ppermute-count + us/call rows)
  serve     continuous-batching serving engine: tokens/s continuous vs
            static batching under bursty arrivals (one decode-step compile
            pinned), TTFT/TPOT percentiles, and the GADGET co-scheduled
            SLO-attainment-vs-training-throughput frontier with per-burst
            worker reclaim

Schedulers are resolved by name through ``repro.sched.registry`` — pass
``--schedulers gadget las+elastic`` to compare a subset, ``--list`` to see
every registered name. All simulations run through the event-driven
``repro.sched.OnlineDriver``.

Scale note: the paper uses S=50, T=200; the default here is a proportionally
scaled instance so the whole suite runs in minutes on one CPU core. Pass
``--full`` for paper-scale settings.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.cluster import make_fat_tree
from repro.cluster.topology import ResourceState
from repro.cluster.trace import JobTraceConfig, generate_jobs
from repro.core.gvne import GvneConfig, solve_slot, solve_slot_exact
from repro.core.problem import DDLJSInstance, ScheduleState
from repro.core.rar_model import profile_from_arch, rar_iteration_time
from repro.sched import ContentionConfig, OnlineDriver, registry

ROWS: List[str] = []

# per-section provenance: every figure records the resolved seeds, scheduler
# names and solver config it actually ran with, so a --json artifact is
# replayable from the artifact alone (no need to read this file at the
# matching revision to learn which seed produced a row)
RUN_META: Dict[str, Dict[str, Any]] = {}

# default comparison set: the paper's four policies plus the beyond-paper
# elastic baseline variants (all resolved through the registry)
DEFAULT_SCHEDULERS = ("gadget", "fifo", "drf", "las",
                      "drf+elastic", "las+elastic")


def emit(name: str, us_per_call: float, derived: str) -> None:
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def record_meta(section: str, **fields: Any) -> None:
    """Merge provenance fields into the section's RUN_META entry."""
    RUN_META.setdefault(section, {}).update(fields)


def _schedulers(seed: int = 0, names: Optional[Sequence[str]] = None):
    return [
        (name, lambda name=name: registry.create(name, seed=seed))
        for name in (names or DEFAULT_SCHEDULERS)
    ]


def _scheduler_meta(seed: int = 0,
                    names: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Resolved scheduler provenance: names, seed, and — when GADGET is in
    the set — the full GvneConfig the registry factory builds for it."""
    resolved = list(names or DEFAULT_SCHEDULERS)
    meta: Dict[str, Any] = {"schedulers": resolved, "scheduler_seed": seed}
    gadget = next((n for n in resolved if n.startswith("gadget")), None)
    if gadget is not None:
        meta["gvne_config"] = dataclasses.asdict(
            registry.create(gadget, seed=seed).cfg)
    return meta


def fig4_total_utility(full: bool = False,
                       schedulers: Optional[Sequence[str]] = None) -> None:
    """Paper Fig. 4: total utility vs number of jobs."""
    n_servers = 50 if full else 16
    horizon = 200 if full else 60
    job_counts = [20, 40, 60, 80, 100] if full else [15, 30, 60, 90]
    record_meta("fig4", n_servers=n_servers, horizon=horizon,
                job_counts=job_counts, graph_seed=1, trace_seed=2,
                **_scheduler_meta(names=schedulers))
    for n_jobs in job_counts:
        graph = make_fat_tree(n_servers=n_servers, seed=1)
        jobs = generate_jobs(JobTraceConfig(
            n_jobs=n_jobs, horizon=horizon,
            mean_interarrival=horizon / max(n_jobs, 1), seed=2))
        inst = DDLJSInstance(graph=graph, jobs=jobs, horizon=horizon)
        for name, mk in _schedulers(names=schedulers):
            t0 = time.perf_counter()
            res = OnlineDriver(inst).run(mk())
            dt = (time.perf_counter() - t0) * 1e6 / horizon
            emit(f"fig4/{name}/jobs={n_jobs}", dt,
                 f"total_utility={res.total_utility:.2f};"
                 f"mean_queue_delay={res.avg_queueing_delay():.2f}")


def fig4b_heavy_load(full: bool = False,
                     schedulers: Optional[Sequence[str]] = None) -> None:
    """Fig. 4 variant at genuine scarcity (jobs need ~10x more iterations than
    the cluster can deliver over the horizon) — the regime where scheduling
    policy separates. GADGET's utility-aware allocation should dominate."""
    n_servers = 50 if full else 16
    horizon = 100 if full else 50
    job_counts = [60, 120] if full else [40, 80]
    record_meta("fig4b", n_servers=n_servers, horizon=horizon,
                job_counts=job_counts, graph_seed=1, trace_seed=5,
                **_scheduler_meta(names=schedulers))
    for n_jobs in job_counts:
        graph = make_fat_tree(n_servers=n_servers, seed=1)
        jobs = generate_jobs(JobTraceConfig(
            n_jobs=n_jobs, horizon=horizon,
            mean_interarrival=horizon / (2.0 * n_jobs),
            zeta_range=(20, 100),
            expected_iters_range=(3000, 30000),
            sensitivity_range=(0.0005, 0.005),
            seed=5))
        inst = DDLJSInstance(graph=graph, jobs=jobs, horizon=horizon)
        for name, mk in _schedulers(names=schedulers):
            t0 = time.perf_counter()
            res = OnlineDriver(inst).run(mk())
            dt = (time.perf_counter() - t0) * 1e6 / horizon
            emit(f"fig4b/{name}/jobs={n_jobs}", dt,
                 f"total_utility={res.total_utility:.2f}")


def _capacity_sweep(kind: str, scales, full: bool) -> None:
    """Embedded-ratio sweep for the registry's default scheduler (gadget)."""
    n_servers = 50 if full else 16
    horizon = 100 if full else 40
    n_jobs = 60 if full else 30
    trials = 3
    record_meta("fig5" if kind == "node" else "fig6",
                n_servers=n_servers, horizon=horizon, n_jobs=n_jobs,
                scales=list(scales), trials=trials,
                graph_seeds=[10 + k for k in range(trials)],
                trace_seeds=[20 + k for k in range(trials)],
                scheduler_seeds=list(range(trials)),
                **{k: v for k, v in
                   _scheduler_meta(names=["gadget"]).items()
                   if k != "scheduler_seed"})
    for scale in scales:
        ratios = []
        dt_us = 0.0
        for trial in range(trials):
            graph = make_fat_tree(n_servers=n_servers, seed=10 + trial)
            if kind == "node":
                # scale GPU capacity per server
                from repro.cluster.topology import Server, SubstrateGraph, Link

                servers = [
                    Server(s.id, s.rack,
                           {r: v * scale for r, v in s.caps.items()})
                    for s in graph.servers
                ]
                links = [Link(u, v, c) for (u, v), c in graph.links.items()]
                graph = SubstrateGraph(servers, links, graph.n_racks, graph.n_core)
            else:
                # scale link bandwidth
                for e in list(graph.links):
                    graph.links[e] *= scale
            jobs = generate_jobs(JobTraceConfig(
                n_jobs=n_jobs, horizon=horizon,
                mean_interarrival=horizon / n_jobs, seed=20 + trial))
            inst = DDLJSInstance(graph=graph, jobs=jobs, horizon=horizon)
            t0 = time.perf_counter()
            res = OnlineDriver(inst).run(registry.create("gadget", seed=trial))
            dt_us += (time.perf_counter() - t0) * 1e6 / horizon
            ratios.append(res.embedded_ratio())
        emit(f"fig{'5' if kind == 'node' else '6'}/capacity_x{scale}",
             dt_us / trials, f"embedded_ratio={np.mean(ratios):.4f}")


def fig5_node_capacity(full: bool = False) -> None:
    """Paper Fig. 5: embedded ratio vs node resource capacity."""
    _capacity_sweep("node", [0.5, 1.0, 2.0, 4.0], full)


def fig6_edge_capacity(full: bool = False) -> None:
    """Paper Fig. 6: embedded ratio vs edge resource capacity."""
    _capacity_sweep("edge", [0.02, 0.1, 0.5, 1.0], full)


def fig7_approx_ratio(full: bool = False) -> None:
    """Paper Fig. 7: per-slot G-VNE utility / exact optimum (HiGHS B&B)."""
    n_inst = 10 if full else 6
    record_meta("fig7", n_instances=n_inst, n_servers=5, n_jobs=5,
                graph_seeds=list(range(n_inst)),
                trace_seeds=[s + 100 for s in range(n_inst)],
                gvne_configs=[dataclasses.asdict(
                    GvneConfig(seed=s, n_candidates=8))
                    for s in range(n_inst)],
                exact_max_servers=3)
    ratios = []
    dt_us = 0.0
    for seed in range(n_inst):
        graph = make_fat_tree(n_servers=5, n_racks=2, n_core=1, seed=seed)
        jobs = generate_jobs(JobTraceConfig(n_jobs=5, horizon=5, seed=seed + 100))
        for j in jobs:
            j.arrival = 0
            j.max_workers = min(j.max_workers, 3)
        inst = DDLJSInstance(graph=graph, jobs=jobs, horizon=5)
        state = ScheduleState(inst)
        t0 = time.perf_counter()
        approx = solve_slot(ResourceState(graph), jobs, state,
                            GvneConfig(seed=seed, n_candidates=8))
        dt_us += (time.perf_counter() - t0) * 1e6
        exact = solve_slot_exact(ResourceState(graph), jobs, state, max_servers=3)
        if exact.value > 1e-9:
            ratios.append(approx.value / exact.value)
    emit("fig7/gvne_vs_exact", dt_us / n_inst,
         f"mean_ratio={np.mean(ratios):.3f};min={np.min(ratios):.3f};"
         f"max={np.max(ratios):.3f};n={len(ratios)}")


def fig8_contention_sweep(full: bool = False) -> None:
    """Beyond-paper: GADGET under shared-bandwidth contention.

    Sweeps the edge oversubscription factor on a bandwidth-scarce cluster
    (links scaled down so rings actually collide on ToR->core edges) and
    reports total utility, peak edge contention (reserved/capacity) and the
    mean fair-share slowdown tau(b_i)/tau(b_eff)."""
    n_servers = 50 if full else 16
    horizon = 100 if full else 40
    n_jobs = 60 if full else 30
    oversubs = [1.0, 1.25, 1.5, 2.0, 3.0] if full else [1.0, 1.5, 2.0]
    record_meta("fig8", n_servers=n_servers, horizon=horizon, n_jobs=n_jobs,
                oversubscription=oversubs, link_scale=0.05,
                graph_seed=7, trace_seed=8,
                **_scheduler_meta(names=["gadget"]))
    for oversub in oversubs:
        graph = make_fat_tree(n_servers=n_servers, seed=7)
        for e in list(graph.links):
            graph.links[e] *= 0.05  # scarce-bandwidth regime (cf. fig6)
        jobs = generate_jobs(JobTraceConfig(
            n_jobs=n_jobs, horizon=horizon,
            mean_interarrival=horizon / (2.0 * n_jobs),
            bandwidth_range=(1e9, 10e9),   # fat rings: force edge sharing
            zeta_range=(20, 100),          # fig4b scarcity regime: utility
            expected_iters_range=(3000, 30000),   # separates under slowdown
            sensitivity_range=(0.0005, 0.005),
            seed=8))
        inst = DDLJSInstance(graph=graph, jobs=jobs, horizon=horizon)
        driver = OnlineDriver(
            inst, contention=ContentionConfig(oversubscription=oversub))
        t0 = time.perf_counter()
        res = driver.run(registry.create("gadget", seed=0))
        dt = (time.perf_counter() - t0) * 1e6 / horizon
        peak = max((r.max_edge_contention for r in res.records), default=0.0)
        mean_cf = float(np.mean([r.mean_contention_factor for r in res.records]))
        emit(f"fig8/oversub_x{oversub}", dt,
             f"total_utility={res.total_utility:.2f};"
             f"embedded_ratio={res.embedded_ratio():.4f};"
             f"peak_edge_contention={peak:.3f};"
             f"mean_contention_factor={mean_cf:.4f}")


def re_ring_cost(full: bool = False) -> None:
    """Mid-slot re-ring vs the paper's checkpoint-preemption model.

    The paper prices a ring-membership change as a preemption: the job stops,
    checkpoints, and restarts from the checkpoint at the new size. The
    elastic path instead re-rings in place — params are replicated over the
    data axis, so reforming over the survivors is a ``device_put`` reshard
    onto the smaller mesh. This sweep measures both on a reduced model over
    8 XLA host devices (spawned as a subprocess; jax must not initialize in
    this parent). Collective mode is psum to keep the warm-up compiles
    cheap — the measured costs (reshard vs ckpt write+read) are
    mode-independent.
    """
    import os
    import subprocess
    import textwrap

    repeats = 5 if full else 3
    record_meta("re_ring", repeats=repeats, arch="qwen3-0.6b (reduced)",
                data_seed=0, devices=8, platform="cpu", ring="w8to4",
                optimizer="sgdm", mode="psum")
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import tempfile, time
        import jax
        from repro.configs import get_arch
        from repro.models.model import build_model
        from repro.data.pipeline import SyntheticTokens
        from repro.training.checkpoint import load_checkpoint, save_checkpoint
        from repro.training.elastic import ElasticTrainer, SlotPlan
        from repro.training.optimizer import make_optimizer

        cfg = get_arch("qwen3-0.6b").reduced()
        model = build_model(cfg)
        data = SyntheticTokens(cfg.vocab, 16, 8, seed=0)
        ckdir = tempfile.mkdtemp(prefix="re_ring_bench_")
        tr = ElasticTrainer(model, make_optimizer("sgdm"), data,
                            global_batch=8, base_lr=1e-2, mode="psum",
                            checkpoint_dir=ckdir)
        tr.run_slot(SlotPlan(workers=4, steps=2))   # warm both ring programs
        tr.run_slot(SlotPlan(workers=8, steps=2))   # (compile outside timing)
        n_params = sum(x.size for x in jax.tree.leaves(tr.params))

        def bench(fn, repeats={repeats}):
            best = float("inf")
            for _ in range(repeats):
                tr.group.form(8)
                tr.params = tr.group.reshard(tr.params)
                tr.opt_state = tr.group.reshard(tr.opt_state)
                jax.block_until_ready(tr.params)
                t0 = time.perf_counter()
                fn()
                jax.block_until_ready(tr.params)
                best = min(best, time.perf_counter() - t0)
            return best

        def re_ring():                         # elastic path: reshard only
            tr.group.re_ring(4)
            tr.params = tr.group.reshard(tr.params)
            tr.opt_state = tr.group.reshard(tr.opt_state)

        def ckpt_preempt():                    # paper path: stop + restore
            save_checkpoint(ckdir, params=tr.params,
                            opt_state=tr.opt_state, step=tr.step)
            tr.restore()
            tr.group.form(4)
            tr.params = tr.group.reshard(tr.params)
            tr.opt_state = tr.group.reshard(tr.opt_state)

        t_re = bench(re_ring)
        t_ck = bench(ckpt_preempt)
        print(f"ROW re_ring_w8to4 {{t_re:.6e}} n_params={{n_params}}")
        print(f"ROW ckpt_preempt_w8to4 {{t_ck:.6e}} n_params={{n_params}}")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # a rehearsal on 8 virtual CPU devices: the child never opens the chip,
    # so it cannot contend with this process (or another) for it
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"re_ring benchmark failed:\n{out.stderr[-2000:]}")
    timed: Dict[str, float] = {}
    for line in out.stdout.splitlines():
        if not line.startswith("ROW "):
            continue
        _, name, seconds, extra = line.split(maxsplit=3)
        timed[name] = float(seconds)
        emit(f"re_ring/{name}", float(seconds) * 1e6,
             f"seconds={float(seconds):.6e};{extra};platform=cpu")
    if "re_ring_w8to4" in timed and "ckpt_preempt_w8to4" in timed:
        ratio = timed["ckpt_preempt_w8to4"] / max(timed["re_ring_w8to4"],
                                                  1e-12)
        emit("re_ring/preempt_over_re_ring", 0.0,
             f"ratio={ratio:.3f};platform=cpu")


def compress_ring_bench(full: bool = False) -> None:
    """Compressed-ring microbench: f32 ring vs XLA int8 ring vs fused ring.

    Times one shard_map'd all-reduce of a d-element gradient on 8 XLA host
    devices (spawned as a subprocess; jax must not initialize in this
    parent) for the three wire layouts, and reports per-worker wire bytes +
    ppermute counts from the shared cost formulas. The fused rows must show
    half the ppermutes per hop of the XLA int8 ring (the single-message
    packed layout) — the same invariant tests/test_wire_cost.py pins on the
    traced jaxpr.

    On top of the original three rows (whose format is pinned — downstream
    artifact diffing relies on it) the bench times the bf16 and fp8 fused
    wires and the 4-bucket overlap pipeline, and derives the overlap mode's
    *exposed* communication: with n buckets launched in reverse-autodiff
    order only the last bucket's chain cannot hide behind backward compute,
    so the pipeline-ideal hidden fraction is (n-1)/n and
    ``exposed = total * (1 - h)`` — the same discount
    ``rar_model.rar_iteration_time(overlap_hidden_fraction=h)`` prices.
    """
    import os
    import subprocess
    import textwrap

    d = (1 << 22) if full else (1 << 18)
    repeats = 20 if full else 8
    n_buckets = 4
    record_meta("compress", d=d, repeats=repeats, devices=8, platform="cpu",
                data_seed=0,
                overlap_n_buckets=n_buckets,
                overlap_hidden_fraction=(n_buckets - 1) / n_buckets,
                wire_modes=["int8", "bf16", "fp8"])
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import time
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import ring_all_reduce
        from repro.dist.compression import compressed_ring_all_reduce

        W, D, REPEATS = 8, {d}, {repeats}
        mesh = jax.make_mesh((W,), ("d",))
        x = jax.random.normal(jax.random.PRNGKey(0), (W, D), jnp.float32)

        def bench(fn, name):
            f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("d", None),
                                      out_specs=P("d", None),
                                      check_vma=False))
            jax.block_until_ready(f(x))          # compile outside timing
            best = float("inf")
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                jax.block_until_ready(f(x))
                best = min(best, time.perf_counter() - t0)
            print(f"ROW {{name}} {{best:.6e}}")

        bench(lambda a: ring_all_reduce(a, "d"), "f32_ring")
        bench(partial(compressed_ring_all_reduce, axis_name="d",
                      fused=False), "xla_int8_ring")
        bench(partial(compressed_ring_all_reduce, axis_name="d",
                      fused=True), "fused_int8_ring")

        from repro.dist.compression import fused_wire_all_reduce
        from repro.dist.overlap import bucketed_ring_reduce

        bench(partial(fused_wire_all_reduce, axis_name="d", wire="bf16"),
              "bf16_fused_ring")
        bench(partial(fused_wire_all_reduce, axis_name="d", wire="fp8"),
              "fp8_fused_ring")

        NB = {n_buckets}
        def overlap(a):
            # the overlap step's wire path: split the gradient into NB
            # equal leaves and ring each bucket through its own chain
            leaves = dict(enumerate(jnp.split(a, NB, axis=-1)))
            out = bucketed_ring_reduce(leaves, "d", variant="int8-fused",
                                       n_buckets=NB)
            return jnp.concatenate([out[k] for k in range(NB)],
                                   axis=-1) / W
        bench(overlap, "overlap_int8_ring")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # a rehearsal on 8 virtual CPU devices: the child never opens the chip,
    # so it cannot contend with this process (or another) for it
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"compress benchmark failed:\n{out.stderr[-2000:]}")

    from repro.core.rar_model import wire_formula
    from repro.dist.collectives import ring_wire_elements
    from repro.dist.compression import (
        compressed_ring_ppermutes,
        compressed_wire_bytes,
        fused_wire_bytes,
    )
    from repro.dist.overlap import plan_bucket_sizes

    w = 8
    formula = wire_formula("int8-fused")
    segs = list(plan_bucket_sizes([d // n_buckets] * n_buckets, n_buckets,
                                  reverse=True))
    costs = {
        "f32_ring": (ring_wire_elements(d, w) * 4.0, 2 * (w - 1)),
        "xla_int8_ring": (compressed_wire_bytes(d, w),
                          compressed_ring_ppermutes(w)),
        "fused_int8_ring": (compressed_wire_bytes(d, w, fused=True),
                            compressed_ring_ppermutes(w, fused=True)),
        "bf16_fused_ring": (fused_wire_bytes(d, w, wire="bf16"),
                            compressed_ring_ppermutes(w, fused=True)),
        "fp8_fused_ring": (fused_wire_bytes(d, w, wire="fp8"),
                           compressed_ring_ppermutes(w, fused=True)),
        "overlap_int8_ring": (
            sum(formula.bytes_per_worker(s, w) for s in segs),
            len(segs) * formula.messages(w)),
    }
    timed: Dict[str, float] = {}
    for line in out.stdout.splitlines():
        if not line.startswith("ROW "):
            continue
        _, name, seconds = line.split()
        timed[name] = float(seconds)
        wire, msgs = costs[name]
        emit(f"compress/{name}", float(seconds) * 1e6,
             f"wire_bytes_per_worker={wire:.0f};ppermutes={msgs};"
             f"ppermutes_per_hop={msgs / (2 * (w - 1)):.1f};d={d};w={w};"
             "platform=cpu")
    if "xla_int8_ring" in timed and "fused_int8_ring" in timed:
        speedup = timed["xla_int8_ring"] / max(timed["fused_int8_ring"], 1e-12)
        emit("compress/fused_over_xla_int8", 0.0,
             f"speedup={speedup:.3f};platform=cpu")
    if "overlap_int8_ring" in timed:
        h = (n_buckets - 1) / n_buckets
        total_us = timed["overlap_int8_ring"] * 1e6
        emit("compress/overlap_exposed_comm", total_us * (1.0 - h),
             f"hidden_fraction={h:.3f};n_buckets={n_buckets};"
             f"total_comm_us={total_us:.1f};d={d};w={w};platform=cpu")


class _TimedScheduler:
    """Delegating wrapper that records each ``schedule_slot`` wall time."""

    def __init__(self, inner):
        self.inner = inner
        self.name = f"{inner.name}+timed"
        self.latencies_s: List[float] = []

    def on_event(self, ev, ctx):
        self.inner.on_event(ev, ctx)

    def schedule_slot(self, ctx):
        t0 = time.perf_counter()
        out = self.inner.schedule_slot(ctx)
        self.latencies_s.append(time.perf_counter() - t0)
        return out


def trace_scale_sweep(
    points: Sequence[int] = (100, 1000, 10_000),
    trace_path: Optional[str] = None,
    horizon: int = 4,
    n_servers: int = 50,
    admission_window: Optional[int] = None,
) -> None:
    """ISSUE 6 scale benchmark: slot-decision latency vs queued-job count.

    Replays a PAI-like trace (``repro.cluster.traces``) with every job queued
    at slot 0 — the backlogged regime where the per-slot hot path is O(active
    jobs) — and reports per-slot decision-latency percentiles for GADGET on
    the paper's S=50 substrate. ``trace_path`` replays a CSV/JSONL trace file
    at its own scale instead of synthesizing the sweep points. The admission
    window (default: cluster GPU capacity — every embedded worker consumes a
    full GPU, so no slot can serve more jobs than that) bounds candidate
    generation; the acceptance bar is median latency < 1 s at 10k queued
    jobs.
    """
    from repro.cluster.traces import (
        jobs_from_trace,
        load_trace,
        synthesize_pai_like,
    )

    graph = make_fat_tree(n_servers=n_servers, seed=1)
    total_gpus = int(graph.total_caps()["gpus"])
    window = admission_window or total_gpus
    record_meta("trace", n_servers=n_servers, horizon=horizon,
                graph_seed=1, synth_seed=3, jobs_seed=4,
                trace_path=trace_path, points=list(points),
                admission_window=window,
                **_scheduler_meta(names=["gadget"]))
    if trace_path:
        traces = [(None, load_trace(trace_path))]
    else:
        traces = [
            (n, synthesize_pai_like(n_jobs=n, horizon=horizon, seed=3,
                                    queued_fraction=1.0))
            for n in points
        ]
    for n, records in traces:
        n = n if n is not None else len(records)
        jobs = jobs_from_trace(records, seed=4)
        inst = DDLJSInstance(graph=graph, jobs=jobs, horizon=horizon)
        sched = registry.create("gadget", seed=0)
        sched.cfg.admission_window = window
        # re-record from the live object: the artifact must show the cfg the
        # run actually used (admission_window is set after the factory)
        record_meta("trace", gvne_config=dataclasses.asdict(sched.cfg))
        timed = _TimedScheduler(sched)
        res = OnlineDriver(inst).run(timed)
        lat_ms = np.array(timed.latencies_s) * 1e3
        emit(f"trace/gadget/jobs={n}", float(np.median(lat_ms)) * 1e3,
             f"p50_ms={np.median(lat_ms):.1f};"
             f"p90_ms={np.percentile(lat_ms, 90):.1f};"
             f"max_ms={lat_ms.max():.1f};"
             f"slots={horizon};window={window};"
             f"workers_placed={sum(r.workers_placed for r in res.records)};"
             f"total_utility={res.total_utility:.2f}")


def serve_bench(full: bool = False) -> None:
    """Continuous-batching serving: engine throughput + SLO co-scheduling.

    Engine half: one bursty request trace served twice on fresh engines —
    continuous batching (admit onto free cache lanes every step) vs static
    batching (admit only after the whole batch drains). Same compiled
    decode step, same requests, same per-call cost; tokens/s differs only
    through the admission policy, and ``decode_compiles`` is pinned == 1
    per engine across every batch composition.

    Scheduler half: a training job and a ``ServeJob`` co-scheduled by
    GADGET on a scarce 4-GPU cluster. Sweeping the SLO weight traces the
    SLO-attainment-vs-training-throughput frontier (attainment from the
    event log vs the training job's accumulated worker-time), and each row
    reports the workers the serving burst reclaimed from the training ring
    through the utility/Eq. (1) pricing.
    """
    import jax

    from repro.cluster.topology import Link, Server, SubstrateGraph
    from repro.configs import get_arch
    from repro.core.problem import Job
    from repro.core.utility import sqrt_utility
    from repro.launch.serve import Request, ServingEngine, serve_requests
    from repro.models.model import build_model
    from repro.sched import (
        DiurnalRequestStream,
        EmbeddingCommitted,
        RequestArrival,
        RequestCompletion,
        RequestStreamConfig,
        ServeSLO,
        ServingBackend,
        make_serve_job,
        slo_attainment_from_events,
    )

    arch = "qwen3-0.6b"
    max_batch = 8 if full else 4
    n_requests = 48 if full else 20
    horizon, burst_start = 16, 6
    weights = [5.0, 20.0, 80.0]
    record_meta("serve", arch=arch, max_batch=max_batch, max_seq=64,
                prefill_chunk=4, n_requests=n_requests, request_seed=11,
                stream_seed=7, horizon=horizon, burst_start=burst_start,
                slo_weights=weights, **_scheduler_meta(names=["gadget"]))
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def request_trace(offset: int = 0) -> List[Request]:
        # bursty arrivals in engine-clock units, re-drawn identically for
        # both admission policies (fresh generator per call)
        rng = np.random.default_rng(11)
        reqs, t = [], offset
        for i in range(n_requests):
            if i % 6 == 0:
                t += int(rng.integers(4, 12))  # gap, then a 6-request burst
            reqs.append(Request(
                id=i,
                prompt=rng.integers(0, cfg.vocab,
                                    size=int(rng.integers(4, 10)),
                                    dtype=np.int32),
                max_new=int(rng.integers(2, 17)), arrival=t))
        return reqs

    for mode, static in (("continuous", False), ("static", True)):
        engine = ServingEngine(model, params, max_batch=max_batch,
                               max_seq=64, prefill_chunk=4)
        # warm the per-engine compiled callables outside the timed region
        # (prefill, decode, lane-zero) so tokens/s compares steady-state
        # serving, not one-off compile time; the trace's arrivals are
        # rebased past the warmup clock so admission dynamics are identical
        serve_requests(engine, [Request(id=-1,
                                        prompt=np.zeros(4, np.int32),
                                        max_new=2, arrival=0)])
        clock0, done0 = engine.clock, len(engine.finished)
        t0 = time.perf_counter()
        serve_requests(engine, request_trace(offset=clock0), static=static)
        wall = time.perf_counter() - t0
        done = engine.finished[done0:]
        calls = max(engine.clock - clock0, 1)
        toks = sum(len(r.tokens) for r in done)
        ttft = np.array([r.ttft_clock for r in done], float)
        tpot = np.array([r.tpot_clock for r in done
                         if r.tpot_clock is not None], float)
        emit(f"serve/engine/{mode}", wall * 1e6 / calls,
             f"tokens_per_s={toks / wall:.1f};"
             f"tokens_per_call={toks / calls:.3f};"
             f"decode_compiles={engine.compile_count};"
             f"ttft_p50={np.percentile(ttft, 50):.1f};"
             f"ttft_p95={np.percentile(ttft, 95):.1f};"
             f"tpot_p50={np.percentile(tpot, 50):.2f};"
             f"tpot_p95={np.percentile(tpot, 95):.2f}")

    # -- co-scheduling frontier: SLO weight vs training throughput ----------
    servers = [Server(i, 0, {"gpus": 2.0, "mem": 8.0}) for i in range(2)]
    links = []
    for s in servers:
        links += [Link(s.node, "r0", 100.0), Link("r0", s.node, 100.0)]
    graph = SubstrateGraph(servers, links, n_racks=1, n_core=0)
    for w in weights:
        train = Job(id=0, arrival=0, max_workers=4,
                    demands={"gpus": 1.0, "mem": 1.0},
                    budgets={"gpus": 500.0}, bandwidth=5.0, zeta=1.0,
                    utility=sqrt_utility(4.0))
        slo = ServeSLO(ttft_slots=2, tpot_slots=1.0, weight=w)
        serve_job = make_serve_job(
            1, arrival=burst_start, offered_tokens=800.0, slo=slo,
            tokens_per_worker_slot=64.0, max_workers=3, bandwidth=5.0)
        inst = DDLJSInstance(graph=graph, jobs=[train, serve_job],
                             horizon=horizon)
        engine = ServingEngine(model, params, max_batch=4, max_seq=32,
                               prefill_chunk=4)
        stream = DiurnalRequestStream(RequestStreamConfig(
            job_id=1, start=burst_start, base_rate=2.0, burst_prob=0.6,
            burst_size=4, prompt_len=(4, 8), max_new=(3, 6), seed=7))
        backend = ServingBackend({1: engine}, tokens_per_worker_slot=64.0)
        t0 = time.perf_counter()
        res = OnlineDriver(inst, events=stream, backend=backend).run("gadget")
        dt = (time.perf_counter() - t0) * 1e6 / horizon
        train_w = {t: 0 for t in range(horizon)}
        serve_w = {t: 0 for t in range(horizon)}
        for e in res.events:
            if isinstance(e, EmbeddingCommitted):
                (train_w if e.job_id == 0 else serve_w)[e.t] += e.n_workers
        burst = range(burst_start, horizon)
        n_arrived = sum(1 for e in res.events
                        if isinstance(e, RequestArrival))
        n_done = sum(1 for e in res.events
                     if isinstance(e, RequestCompletion))
        att = slo_attainment_from_events(res.events, 1, slo)
        # completion-based attainment (the sanitizer-checked metric) is
        # blind to backlogged requests; the frontier metric scores met
        # completions against the whole offered load, so starving the
        # serve job shows up instead of vanishing from the denominator
        offered_att = att * n_done / max(n_arrived, 1)
        emit(f"serve/frontier/weight={w:g}", dt,
             f"slo_attainment={att:.3f};"
             f"offered_attainment={offered_att:.3f};"
             f"requests={n_done}/{n_arrived};"
             f"train_worker_time={res.state.z[0]:.1f};"
             f"train_min_workers_burst={min(train_w[t] for t in burst)};"
             f"serve_peak_workers={max(serve_w[t] for t in burst)};"
             f"reclaimed_workers="
             f"{train_w[burst_start - 1] - min(train_w[t] for t in burst)};"
             f"served_tokens={sum(r.get('served_tokens', 0) for r in backend.reports)};"
             f"decode_compiles={engine.compile_count}")


def eq1_rar_time_model(full: bool = False) -> None:
    """§III-3 table: tau(w) for a 1.2B-param job on v5e constants."""
    prof = profile_from_arch(n_params=1.2e9, tokens_per_batch=4096 * 8)
    record_meta("eq1", n_params=1.2e9, tokens_per_batch=4096 * 8,
                workers=[1, 2, 4, 8, 16, 32])
    for w in (1, 2, 4, 8, 16, 32):
        t0 = time.perf_counter()
        tau = float(prof.iteration_time(w))
        dt = (time.perf_counter() - t0) * 1e6
        emit(f"eq1/tau_w={w}", dt, f"tau_s={tau:.4f}")


FIGS = {
    "fig4": fig4_total_utility,
    "fig4b": fig4b_heavy_load,
    "fig5": fig5_node_capacity,
    "fig6": fig6_edge_capacity,
    "fig7": fig7_approx_ratio,
    "fig8": fig8_contention_sweep,
    "eq1": eq1_rar_time_model,
    "re_ring": re_ring_cost,
    "compress": compress_ring_bench,
    "serve": serve_bench,
}

# figures that compare schedulers and therefore honor --schedulers
COMPARISON_FIGS = {"fig4", "fig4b"}


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--only", nargs="*", choices=sorted(FIGS), default=None)
    parser.add_argument("--full", action="store_true",
                        help="paper-scale settings (slow)")
    parser.add_argument("--list", action="store_true",
                        help="list registered scheduler names and exit")
    parser.add_argument("--schedulers", nargs="+", metavar="NAME",
                        default=None,
                        help="scheduler names (repro.sched.registry) for the "
                             "comparison figures; default: "
                             + " ".join(DEFAULT_SCHEDULERS))
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="also dump the rows as a JSON artifact")
    parser.add_argument("--trace", nargs="?", const=True, default=None,
                        metavar="PATH",
                        help="trace-replay benchmark: with PATH, replay that "
                             "CSV/JSONL trace (repro.cluster.traces schema); "
                             "bare, synthesize PAI-like workloads at the "
                             "--scale-points sizes")
    parser.add_argument("--scale-sweep", action="store_true",
                        help="run the queued-job scale sweep (implies "
                             "--trace)")
    parser.add_argument("--scale-points", nargs="+", type=int,
                        default=[100, 1000, 10_000], metavar="N",
                        help="queued-job counts for the scale sweep")
    args = parser.parse_args()
    if args.list:
        for name in registry.available():
            print(name)
        return
    for name in args.schedulers or ():
        if name not in registry.available():
            parser.error(f"unknown scheduler {name!r}; --list shows the "
                         "registered names")
    if args.schedulers:
        selected = set(args.only or FIGS)
        if not selected & COMPARISON_FIGS:
            parser.error("--schedulers only applies to the comparison "
                         f"figures ({', '.join(sorted(COMPARISON_FIGS))}); "
                         "the selected figures ignore it")
        if selected - COMPARISON_FIGS:
            print("# note: --schedulers applies to the comparison figures "
                  "only; other figures run their fixed scheduler",
                  file=sys.stderr)
    print("name,us_per_call,derived")
    if args.trace is not None or args.scale_sweep:
        trace_scale_sweep(
            points=args.scale_points,
            trace_path=args.trace if isinstance(args.trace, str) else None,
        )
    else:
        for name, fn in FIGS.items():
            if args.only and name not in args.only:
                continue
            if name in COMPARISON_FIGS:
                fn(full=args.full, schedulers=args.schedulers)
            else:
                fn(full=args.full)
    if args.json:
        import json

        def _num(v: str):
            try:
                return float(v)
            except ValueError:
                return v

        rows = []
        for row in ROWS:
            name, us, derived = row.split(",", 2)
            rows.append({
                "name": name,
                "us_per_call": float(us),
                **{k: _num(v) for k, v in
                   (kv.split("=", 1) for kv in derived.split(";") if "=" in kv)},
            })
        artifact = {
            "meta": {
                "argv": sys.argv[1:],
                "full": args.full,
                "sections": RUN_META,
            },
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"# wrote {len(rows)} rows + {len(RUN_META)} section metas "
              f"-> {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
