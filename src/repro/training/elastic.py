"""Elastic data-parallel training — the consumer of GADGET's per-slot worker
counts.

GADGET reallocates workers between slots (preemptive jobs, §IV). The trainer
maps worker count w -> DP degree: between slots it reforms the ring over the
first w devices, reshards params/optimizer (device_put — same bytes, new
layout), and continues from the exact step. A slot with w=0 parks the job
(checkpoint only).

Two layers:

  * :class:`RingWorkerGroup` — the reusable ring substrate: owns the mesh and
    a compiled-step cache keyed by ``(workers, mode)`` so back-to-back slots
    at the same ring size reuse the jitted executable instead of re-tracing,
    and exposes :meth:`RingWorkerGroup.re_ring` — reform the ring over the
    surviving workers *mid-slot* (a ``device_put`` reshard onto the smaller
    mesh; the survivors already hold full replicas, so no checkpoint restore
    is involved).
  * :class:`ElasticTrainer` — per-job training state (params, optimizer,
    step counter, loss history) driven slot-by-slot through the group. A
    :class:`SlotPlan` may carry a scripted mid-slot ``leave``; the trainer
    then re-rings and finishes the slot on the survivors at the same global
    batch.

Worker counts are clamped to the largest divisor of ``global_batch`` that
fits the device count (:func:`largest_feasible_ring`): a non-divisor DP
degree would shard the ``P("data")`` batch axis unevenly, which XLA rejects.

The data pipeline is step-indexed and deterministic, so token order is
independent of the DP degree (verified in tests): elasticity changes
throughput, never the training trajectory at fixed global batch.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.registry import STEP_MODES
from repro.launch.runtime import span
from repro.training.checkpoint import load_checkpoint, save_checkpoint
from repro.training.optimizer import Optimizer
from repro.training.train_step import make_ring_train_step


def largest_feasible_ring(requested: int, *, global_batch: int,
                          n_devices: int) -> int:
    """Largest ring size <= ``requested`` that divides ``global_batch`` and
    fits on ``n_devices`` (0 when ``requested`` <= 0).

    The DP degree must divide the global batch: ``P("data")`` shards the
    batch axis evenly or not at all, so e.g. ``global_batch=8, workers=3``
    clamps to 2 (the largest divisor of 8 that is <= 3).
    """
    w = min(int(requested), int(n_devices), int(global_batch))
    if w <= 0:
        return 0
    while global_batch % w:
        w -= 1
    return w


@dataclasses.dataclass
class SlotPlan:
    """One scheduler decision: train for ``steps`` with ``workers`` workers.

    ``leave=(after, n)`` scripts a mid-slot membership change: after ``after``
    completed steps, ``n`` workers depart and the slot finishes on the
    survivors via :meth:`RingWorkerGroup.re_ring` (same global batch, no
    checkpoint restore).
    """

    workers: int
    steps: int
    leave: Optional[Tuple[int, int]] = None


@dataclasses.dataclass
class _RingProgram:
    """One compiled ring configuration: mesh + jitted step + shardings."""

    mesh: Mesh
    step_fn: object              # jitted shard_map train step
    replicated: NamedSharding    # P() over the mesh (params / opt state)
    batch_sharding: NamedSharding  # P("data") over the mesh


class RingWorkerGroup:
    """Mesh + compiled-step cache for one job's elastic ring.

    The cache is keyed by ``(workers, mode, n_buckets, wire_dtype)``;
    ``compile_count`` counts cache misses (each miss builds a fresh
    ``jax.jit(jax.shard_map(...))`` — the expensive trace/compile path), so
    equal-sized back-to-back slots can be asserted to reuse the executable.
    ``mode`` is any :func:`~repro.training.train_step.make_ring_train_step`
    ring mode, including ``"compressed-fused"`` (the Pallas single-ppermute
    hop pipeline of :mod:`repro.dist.compression`), its ``"bf16-fused"`` /
    ``"fp8-fused"`` wire-format siblings, and
    ``"compressed-fused-overlap"`` (per-bucket rings in reverse-autodiff
    order; ``n_buckets`` overrides the registry default bucket count).
    """

    # attributes make_ring_train_step closes over at _program build time:
    # they are part of the compiled step's semantics but NOT part of the
    # (workers, mode, n_buckets, wire_dtype) cache key, so they must never
    # change after __init__ — a mutation would silently serve stale compiled
    # steps (or, if jit retraced on it, turn the cache into per-slot
    # recompiles). The static verifier (repro.analysis.collectives) checks
    # by AST that no method other than __init__ assigns them, and
    # audit_compiled_step_cache cross-checks the live fingerprint per slot.
    STATIC_CLOSURE_ATTRS = ("model", "optimizer", "global_batch", "lr",
                            "n_buckets", "wire_dtype")

    def __init__(self, model, optimizer: Optimizer, *, global_batch: int,
                 lr: float, mode: str = "ring",
                 n_buckets: Optional[int] = None):
        self.model = model
        self.optimizer = optimizer
        self.global_batch = global_batch
        self.lr = lr
        self.mode = mode
        spec = STEP_MODES.get(mode)
        # resolved bucket count (None for non-overlap modes) and wire payload
        # dtype: both change the traced collectives, so both sit in the
        # cache key alongside mode
        self.n_buckets = (spec.n_buckets if spec is not None else None) \
            if n_buckets is None else int(n_buckets)
        self.wire_dtype = spec.wire_dtype if spec is not None else "float32"
        self.workers = 0                 # current ring size (0 = unformed)
        self.compile_count = 0           # compiled-step cache misses
        self._programs: Dict[Tuple[int, str, Optional[int], str],
                             _RingProgram] = {}
        self._warm: set = set()          # keys whose step_fn has run >= once
        self._closure_fingerprint = self.closure_fingerprint()

    def cache_key(self, workers: int) -> Tuple[int, str, Optional[int], str]:
        """The compiled-step cache key for a (clamped) ring size.

        Everything else the jitted step depends on is closure state fixed at
        construction (``STATIC_CLOSURE_ATTRS``), so
        ``(workers, mode, n_buckets, wire_dtype)`` uniquely identifies an
        executable — the invariant
        ``repro.sched.backend.audit_compiled_step_cache`` verifies. The
        first element stays the worker count (the audit relies on it).
        """
        return (int(workers), self.mode, self.n_buckets, self.wire_dtype)

    def closure_fingerprint(self) -> Tuple:
        """Identity snapshot of the closed-over static attrs (audit hook)."""
        return (id(self.model), id(self.optimizer),
                int(self.global_batch), float(self.lr),
                self.n_buckets, self.wire_dtype)

    # -- ring formation -----------------------------------------------------
    def resolve_workers(self, requested: int) -> int:
        """Clamp a requested worker count to a feasible ring size."""
        return largest_feasible_ring(requested,
                                     global_batch=self.global_batch,
                                     n_devices=len(jax.devices()))

    def form(self, workers: int) -> int:
        """Form (or re-form) the ring at the clamped size; returns it."""
        w = self.resolve_workers(workers)
        if w <= 0:
            raise ValueError(f"cannot form a ring for workers={workers}")
        self._program(w)
        self.workers = w
        return w

    def re_ring(self, survivors: int) -> int:
        """Reform the ring over ``survivors`` workers mid-slot.

        This is the elastic shrink/grow path: the new mesh spans the first
        ``survivors`` devices, and because params/opt state are replicated
        over the data axis, moving onto it is a plain ``device_put`` reshard
        (see :meth:`reshard`) — no checkpoint restore, no lost progress.
        """
        return self.form(max(1, survivors))

    def _program(self, w: int) -> _RingProgram:
        key = self.cache_key(w)
        prog = self._programs.get(key)
        if prog is None:
            mesh = Mesh(np.array(jax.devices()[:w]), ("data",))
            step_fn = make_ring_train_step(
                self.model, self.optimizer, "data", lr=self.lr,
                mode=self.mode,
                n_buckets=self.n_buckets
                if self.mode == "compressed-fused-overlap" else None)
            # params and optimizer state are donated: the step writes the
            # new state into their buffers instead of holding two copies
            # (a full-width adamw state does not fit twice on one chip)
            smapped = jax.jit(jax.shard_map(
                step_fn, mesh=mesh,
                in_specs=(P(), P(), P("data")),
                out_specs=(P(), P(), P()),
                check_vma=False,
            ), donate_argnums=(0, 1))
            prog = _RingProgram(
                mesh=mesh,
                step_fn=smapped,
                replicated=NamedSharding(mesh, P()),
                batch_sharding=NamedSharding(mesh, P("data")),
            )
            self._programs[key] = prog
            self.compile_count += 1
        return prog

    # -- execution over the current ring ------------------------------------
    @property
    def _current(self) -> _RingProgram:
        if self.workers <= 0:
            raise RuntimeError("ring not formed; call form() first")
        return self._programs[self.cache_key(self.workers)]

    def reshard(self, tree):
        """Replicate a pytree over the current mesh (elastic reshard: same
        bytes, new device set)."""
        return jax.device_put(tree, self._current.replicated)

    def shard_batch(self, batch):
        """Split a global batch across the current ring's data axis."""
        sh = self._current.batch_sharding
        return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), sh),
                            batch)

    @property
    def warm(self) -> bool:
        """True once the current ring's step has executed at least once —
        i.e. its wall time no longer includes the trace/compile."""
        return self.cache_key(self.workers) in self._warm

    def step(self, params, opt_state, batch):
        """Run one compiled train step over the current ring.

        ``params`` and ``opt_state`` are donated: after the call they are
        deleted, and the caller must continue from the returned state.
        """
        out = self._current.step_fn(params, opt_state, batch)
        self._warm.add(self.cache_key(self.workers))
        return out


class ElasticTrainer:
    """Runs a job across slots with varying DP degree on host devices.

    ``params`` and ``opt_state`` always hold the live training state: every
    step donates the previous arrays to the compiled step and rebinds both
    attributes to its outputs, so a reference taken before a step is dead
    after it. ``param_dtype`` is the dtype parameters are initialized and
    trained in (optimizer moments stay float32 whatever it is).
    """

    def __init__(self, model, optimizer: Optimizer, data, *,
                 global_batch: int, base_lr: float = 1e-3,
                 mode: str = "ring", checkpoint_dir: Optional[str] = None,
                 n_buckets: Optional[int] = None,
                 param_dtype=jnp.float32):
        self.model = model
        self.optimizer = optimizer
        self.data = data
        self.global_batch = global_batch
        self.base_lr = base_lr
        self.mode = mode
        self.checkpoint_dir = checkpoint_dir
        self.group = RingWorkerGroup(model, optimizer,
                                     global_batch=global_batch,
                                     lr=base_lr,  # fixed global batch =>
                                     mode=mode,   # fixed LR (w splits only)
                                     n_buckets=n_buckets)
        self.params = model.init(jax.random.PRNGKey(0), dtype=param_dtype)
        self.opt_state = optimizer.init(self.params)
        self.step = 0
        self.losses: List[float] = []
        self.resharding_events = 0   # slot-boundary mesh changes
        self.re_ring_events = 0      # mid-slot re-rings (no ckpt restore)
        self.restores = 0            # checkpoint restores (failure recovery)

    def _reshard_state(self) -> None:
        self.params = self.group.reshard(self.params)
        self.opt_state = self.group.reshard(self.opt_state)

    def run_slot(self, plan: SlotPlan) -> Dict[str, float]:
        """Execute one slot; returns measured outcomes.

        Keys: ``steps`` (executed), ``loss`` (last), ``workers`` (initial
        clamped ring size), ``worker_steps`` (sum of ring size over executed
        steps — the measured worker-time numerator), ``timings`` (ring size
        -> best wall seconds/step), ``re_rings`` (mid-slot re-rings).
        """
        if plan.workers <= 0:
            self._checkpoint(0)
            return {"steps": 0, "loss": float("nan")}
        with span("train.form", workers=plan.workers):
            w = self.group.form(plan.workers)
            self._reshard_state()
        self.resharding_events += 1

        segments: List[Tuple[int, int]] = [(w, plan.steps)]
        if plan.leave is not None:
            after, n_leave = plan.leave
            after = max(0, min(int(after), plan.steps))
            survivors = self.group.resolve_workers(max(1, w - int(n_leave)))
            segments = [(w, after), (survivors, plan.steps - after)]

        loss = float("nan")
        worker_steps = 0
        re_rings = 0
        timings: Dict[int, float] = {}
        for idx, (seg_w, seg_steps) in enumerate(segments):
            if idx > 0:
                with span("train.re_ring", workers=seg_w):
                    seg_w = self.group.re_ring(seg_w)
                    self._reshard_state()
                self.re_ring_events += 1
                re_rings += 1
            for _ in range(seg_steps):
                with span("train.step", step=self.step, workers=seg_w):
                    loss, dt, was_warm = self._step()
                if was_warm:  # a cold step times the trace/compile, not the
                    # ring — never report it (it would poison calibration)
                    timings[seg_w] = min(timings.get(seg_w, float("inf")), dt)
                self.losses.append(loss)
                self.step += 1
                worker_steps += seg_w
        self._checkpoint(w)
        return {"steps": plan.steps, "loss": loss, "workers": w,
                "worker_steps": worker_steps, "timings": timings,
                "re_rings": re_rings}

    def _step(self) -> Tuple[float, float, bool]:
        """One train step on the current ring: its loss, the seconds from
        dispatch to the loss on the host, and whether the step was compiled
        before it ran."""
        with span("train.input"):
            # step-indexed: elastic-safe
            batch = self.group.shard_batch(self.data.batch(self.step))
        was_warm = self.group.warm
        t0 = time.perf_counter()
        with span("train.dispatch"):
            self.params, self.opt_state, metrics = self.group.step(
                self.params, self.opt_state, batch)
        with span("train.sync"):
            loss = float(metrics["loss"])  # sync: timing covers the step
        return loss, time.perf_counter() - t0, was_warm

    def _checkpoint(self, workers: int) -> None:
        if self.checkpoint_dir:
            with span("train.checkpoint", workers=workers):
                save_checkpoint(self.checkpoint_dir, params=self.params,
                                opt_state=self.opt_state, step=self.step)

    def restore(self) -> bool:
        if not self.checkpoint_dir:
            return False
        with span("train.restore", workers=self.group.workers):
            try:
                params, opt, step, _ = load_checkpoint(self.checkpoint_dir)
            except FileNotFoundError:
                return False
            self.params = jax.tree.map(jnp.asarray, params)
            self.opt_state = jax.tree.map(jnp.asarray, opt)
        self.step = step
        self.restores += 1
        return True
