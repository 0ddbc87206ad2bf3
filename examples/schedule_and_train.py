"""End-to-end: GADGET schedules real JAX training jobs (the paper's loop).

GADGET's per-slot decisions (ring size w per job) drive *actual* elastic
ring-all-reduce data-parallel training of reduced-config models on host
devices, now through the execution-backend API: one ``OnlineDriver`` slot
loop, a ``LiveBackend`` that binds each committed ring to its job's
``ElasticTrainer``, a scripted mid-slot ``WorkerLeave`` that shrinks a ring
in place (re-ring, no checkpoint restore), and measured step timings fed
back through ``repro.cluster.calibrate`` so each job's Eq. (1) bandwidth
tracks what the hardware actually delivers.

Usage:  PYTHONPATH=src python examples/schedule_and_train.py
(sets its own XLA_FLAGS before importing jax — run as its own process)
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import tempfile

from repro.cluster import make_fat_tree
from repro.core.problem import DDLJSInstance, Job
from repro.core.rar_model import profile_from_arch
from repro.core.utility import sqrt_utility
from repro.configs import get_arch
from repro.data.pipeline import SyntheticTokens
from repro.launch.runtime import enable_compile_cache
from repro.models.model import build_model
from repro.sched import (
    ContentionConfig,
    LiveBackend,
    OnlineDriver,
    ScriptedEventStream,
    WorkerLeave,
    registry,
)
from repro.training.elastic import ElasticTrainer
from repro.training.optimizer import make_optimizer

ARCHS = ["qwen3-0.6b", "granite-3-2b", "rwkv6-7b"]
SLOTS = 6
STEPS_PER_SLOT = 4
OVERSUBSCRIPTION = 1.5  # admit rings beyond edge capacity; fair-share the link


def make_jobs():
    jobs = []
    for i, arch in enumerate(ARCHS):
        cfg = get_arch(arch)
        # job 1 trains over the fused int8 ring (the trainer mode below is
        # derived from this field), so its Eq. (1) profile prices the
        # compressed wire bytes and the single-ppermute hop; the uniform
        # per-ppermute latency makes the halved message count visible
        prof = profile_from_arch(n_params=float(cfg.n_params()),
                                 tokens_per_batch=4096.0 * 8,
                                 compression="int8-fused" if i == 1 else None,
                                 message_overhead=5e-6)
        jobs.append(Job(
            id=i, arrival=i % 2, max_workers=4,
            demands={"gpus": 1.0, "mem": 1.0},
            budgets={"gpus": 40.0},
            bandwidth=30e9,  # heavy enough that rings contend on uplinks
            zeta=float(prof.iterations_per_slot(4, 60.0)) / 4.0,
            utility=sqrt_utility(10.0),
            profile=prof, arch=arch,
        ))
    return jobs


def main() -> None:
    enable_compile_cache()
    # 1-2 GPUs per server: rings must span servers and share uplinks, so the
    # contention re-pricing actually engages (colocated rings never contend)
    graph = make_fat_tree(n_servers=4, n_racks=2, n_core=1,
                          gpus_choices=(1, 2), seed=0)
    jobs = make_jobs()
    inst = DDLJSInstance(graph=graph, jobs=jobs, horizon=SLOTS)

    trainers = {}
    for job in jobs:
        cfg = get_arch(job.arch).reduced()
        model = build_model(cfg)
        data = SyntheticTokens(cfg.vocab, seq_len=32, global_batch=8,
                               seed=job.id)
        # the trainer runs whatever ring the job's profile prices: a
        # profile with compression="int8-fused" (job 1 above) trains over
        # the fused single-ppermute int8 ring, the rest stay on the
        # paper-faithful f32 ring — pricing and execution cannot drift
        mode = {"int8": "compressed", "int8-fused": "compressed-fused",
                "bf16-fused": "bf16-fused", "fp8-fused": "fp8-fused"}.get(
            job.profile.compression, "ring")
        trainers[job.id] = ElasticTrainer(
            model, make_optimizer("adamw"), data, global_batch=8,
            base_lr=3e-3, mode=mode,
            checkpoint_dir=tempfile.mkdtemp(prefix=f"job{job.id}_"))

    print(f"== GADGET driving elastic RAR training of {ARCHS} ==")
    before = {j.id: j.profile.bandwidth for j in jobs}
    backend = LiveBackend(trainers, steps_per_slot=STEPS_PER_SLOT)
    driver = OnlineDriver(
        inst,
        contention=ContentionConfig(oversubscription=OVERSUBSCRIPTION),
        # a scripted mid-slot departure: one of job 0's workers leaves in
        # slot 3 and the ring re-forms around the survivors (no restore)
        events=ScriptedEventStream(mid=[WorkerLeave(3, job_id=0, n=1)]),
        backend=backend,
    )
    result = driver.run(registry.create("gadget", seed=0))

    by_slot = {}
    for row in backend.reports:
        by_slot.setdefault(row["t"], {})[row["job_id"]] = row
    for t in range(SLOTS):
        line = []
        for job in jobs:
            if t < job.arrival:
                line.append(f"{job.arch}: not-arrived")
                continue
            row = by_slot.get(t, {}).get(job.id)
            if row is None:
                line.append(f"{job.arch}: preempted(ckpt)")
                continue
            tag = f"w={row['workers']} loss={row['loss']:.3f}"
            if row.get("re_rings"):
                tag += f" re-ring(x{row['re_rings']})"
            if row["factor"] < 0.999:
                tag += f" measured(x{row['factor']:.2f})"
            line.append(f"{job.arch}: {tag}")
        print(f" slot {t}: " + " | ".join(line))

    print("\n== outcome ==")
    for job in jobs:
        tr = trainers[job.id]
        first = tr.losses[0] if tr.losses else float("nan")
        last = tr.losses[-1] if tr.losses else float("nan")
        cal = backend.calibrated.get(job.id)
        cal_tag = (f", calibrated b {before[job.id]:.2e}->{cal:.2e} elem/s"
                   if cal is not None else "")
        print(f"  {job.arch}: steps={tr.step} loss {first:.3f} -> {last:.3f} "
              f"(reshards={tr.resharding_events}, "
              f"re-rings={tr.re_ring_events}, "
              f"worker-time={result.state.z[job.id]:.1f}{cal_tag})")
        assert not tr.losses or last < first + 1e-6, "training should improve"
    assert trainers[0].re_ring_events or not by_slot.get(3, {}).get(0), \
        "the scripted WorkerLeave should have re-rung job 0's slot-3 ring"


if __name__ == "__main__":
    main()
