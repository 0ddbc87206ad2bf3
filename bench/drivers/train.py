"""Training cells: GADGET in ``OnlineDriver`` with ``LiveBackend`` drives
the program's ``ElasticTrainer`` slot after slot until the window closes.

Set-up builds one trainer, one backend and one scheduler, and drives the
trainer from the seeded weights through its first steps in that backend's
first slot (cut short once the compared steps have run), with this
module's batches: those steps compile and warm
the step, and the state they leave is what the plain reference is compared
with after the window. The window then runs on the same objects.
"""

from __future__ import annotations

import functools
import gc
from typing import Callable, Dict, List, Optional

import numpy as np

import devtrace
import flops
import harness
import lm
from harness import Limit, Run, StopWindow, now


class TokenBatches:
    """Step-indexed batches from the seed: a random walk over the
    vocabulary in each row, every row and step different. ``batch()``
    marks each step on the host clock, and raises :class:`StopWindow` once
    the window it is armed with has closed, or at step ``stop_at``. Before
    that it calls ``on_step(step)``, when the steps before have run and
    this one has not."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int):
        self.vocab, self.seq, self.rows, self.seed = vocab, seq, batch, seed
        self.deadline: Optional[float] = None
        self.stop_at: Optional[int] = None
        self.on_step: Optional[Callable[[int], None]] = None
        self.marks: List[float] = []        # batch() returns, in the window
        self.stop_time: Optional[float] = None

    def make(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng((self.seed, step))
        start = rng.integers(0, self.vocab, size=(self.rows, 1))
        walk = rng.integers(-3, 4, size=(self.rows, self.seq - 1))
        tokens = np.mod(np.concatenate([start, walk], 1).cumsum(1),
                        self.vocab).astype(np.int32)
        labels = np.concatenate([tokens[:, 1:], tokens[:, :1]], 1)
        return {"tokens": tokens, "labels": labels.astype(np.int32)}

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        if self.on_step is not None:
            self.on_step(step)
        if self.stop_at is not None and step >= self.stop_at:
            raise StopWindow
        t = now()
        if self.deadline is not None and t >= self.deadline:
            self.stop_time = t
            raise StopWindow
        out = self.make(step)
        if self.deadline is not None:
            self.marks.append(now())
        return out


class TimedBackend:
    """The backend as the driver sees it, with each ``execute_slot``
    timed."""

    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
        self.spans: List[tuple] = []          # (start, end) of each slot

    def execute_slot(self, decision, execution):
        t0 = now()
        out = self.inner.execute_slot(decision, execution)
        self.spans.append((t0, now()))
        return out


class TimedScheduler:
    """The scheduler with each ``schedule_slot`` timed."""

    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
        self.spans: List[tuple] = []

    def on_event(self, ev, ctx):
        self.inner.on_event(ev, ctx)

    def schedule_slot(self, ctx):
        t0 = now()
        out = self.inner.schedule_slot(ctx)
        self.spans.append((t0, now()))
        return out


def _instance(arch_name: str, n_params: int, tokens: int, horizon: int):
    from repro.cluster import make_fat_tree
    from repro.core.problem import DDLJSInstance, Job
    from repro.core.rar_model import profile_from_arch
    from repro.core.utility import sqrt_utility

    job = Job(id=0, arrival=0, max_workers=1,
              demands={"gpus": 1.0, "mem": 1.0}, budgets={"gpus": 1e9},
              bandwidth=1e9, zeta=1.0, utility=sqrt_utility(10.0),
              profile=profile_from_arch(n_params=float(n_params),
                                        tokens_per_batch=float(tokens)),
              arch=arch_name)
    graph = make_fat_tree(n_servers=1, n_racks=1, n_core=1,
                          gpus_choices=(1,), seed=0)
    return DDLJSInstance(graph=graph, jobs=[job], horizon=horizon)


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return dict(zip(lm.leaf_paths(tree), map(float, norms)))


def run(r: Run, devices, arch_override=None, break_step=None,
        stand_in: Optional[Dict] = None) -> None:
    """Fill ``r``. ``arch_override`` and ``break_step`` are for the tests
    under ``bench/tests``: a small model on the CPU, and a fault planted in
    the timed path. With ``stand_in`` the plain reference, run with those
    arguments of :func:`reference_steps` (``{"quant": "fp8"}``, the
    control), takes the program's place in the comparison."""
    import jax
    import jax.numpy as jnp

    from repro.sched import LiveBackend, OnlineDriver, registry
    from repro.training.elastic import ElasticTrainer
    from repro.training.optimizer import make_optimizer

    c, tr = r.config, r.traffic
    opt = tr["optimizer"]
    batch, seq = tr["global_batch"], tr["seq"]
    key = harness.key_from_seed(r.seed)
    model = lm.program_model(c, arch_override)
    params = lm.seeded_params(c, model, key)
    model.init = lambda key, dtype=None: params   # the trainer's weights
    data = TokenBatches(c["vocab_size"], seq, batch, r.seed)
    trainer = ElasticTrainer(model, make_optimizer(opt["name"]), data,
                             global_batch=batch, base_lr=opt["lr"],
                             mode=tr["mode"], param_dtype=jnp.bfloat16)
    del params, model.init
    if break_step is not None:
        break_step(trainer)
    n_params = int(sum(x.size for x in jax.tree.leaves(trainer.params)))
    sched = registry.create("gadget", seed=0)

    backend = TimedBackend(LiveBackend(
        {0: trainer}, steps_per_slot=tr["steps_per_slot"]))
    timed = TimedScheduler(sched)

    # -- the first steps: the window's own backend, scheduler and feed ----
    first = tr["compared_steps"]
    seen: Dict[str, object] = {}

    def snapshot(step: int) -> None:
        if step == 1:   # the first gradient, as adamw's first moment holds it
            seen["g1"] = {k: v / (1.0 - opt["b1"]) for k, v in
                          _leaf_norms(trainer.opt_state["m"]).items()}
        if step == first:   # before the next step donates them
            seen["p"] = jax.device_get(trainer.params)

    data.on_step, data.stop_at = snapshot, first
    try:
        OnlineDriver(_instance(c["program_arch"], n_params, batch * seq,
                               10 ** 9), backend=backend).run(timed)
    except StopWindow:
        pass
    data.on_step = data.stop_at = None
    if trainer.step != first or len(seen) != 2:
        raise RuntimeError(f"set-up ran {trainer.step} steps, not {first}")
    losses = list(trainer.losses[:first])
    backend.spans.clear()
    timed.spans.clear()

    # -- the window --------------------------------------------------------
    driver = OnlineDriver(_instance(c["program_arch"], n_params,
                                    batch * seq, 10 ** 9), backend=backend)
    r.open_window()
    data.deadline = r.t_window + r.seconds
    try:
        with devtrace.span("train_window"):
            driver.run(timed)
    except StopWindow:
        pass
    r.t_window_end = data.stop_time
    r.close_window()
    steps = len(data.marks)
    r.attempted = steps
    r.counters.update(steps=steps, tokens=steps * batch * seq,
                      slots=len(backend.spans),
                      flops_per_token=flops.train_flops_per_token(c, seq))
    _slot_overheads(r, backend, timed, data)
    gaps = np.diff(data.marks)      # batch to batch: a step, and any slot edge
    if len(gaps):
        med = float(np.median(gaps))
        r.note(step_s_min=float(gaps.min()), step_s_median=med,
               step_s_max=float(gaps.max()),
               steps_over_median_by_5pct=int((gaps > 1.05 * med).sum()))
        # where in the window each slow step began, and what it took
        slow = np.flatnonzero(gaps > 1.05 * med)
        r.note(slow_steps=[[float(data.marks[k] - r.t_window),
                            float(gaps[k])] for k in slow[:20]])
    r.note(window_steps=steps, window_slots=len(backend.spans),
           window_s=r.window_s, losses_first=losses,
           last_loss=trainer.losses[-1], compiles=trainer.group.compile_count)
    r.memory_peak_bytes = harness.memory_peak_bytes(devices)

    # -- free the program's state, then the reference -----------------------
    del trainer, driver, backend, data
    gc.collect()
    _compare(r, c, tr, key, losses, seen, stand_in)


def _slot_overheads(r: Run, backend: TimedBackend, timed: TimedScheduler,
                    data: TokenBatches) -> None:
    """Per slot that ended inside the window: its ``schedule_slot`` time
    plus the part of its ``execute_slot`` not spent in train steps (a step
    runs from its batch's return to the next batch request)."""
    asks = data.marks
    for (s0, s1), (e0, e1) in zip(timed.spans, backend.spans):
        in_slot = [t for t in asks if e0 <= t <= e1]
        if not in_slot:
            continue
        step_time = 0.0
        for k, t in enumerate(in_slot):
            nxt = in_slot[k + 1] if k + 1 < len(in_slot) else e1
            step_time += nxt - t
        r.add("slot_overhead_s", (s1 - s0) + (e1 - e0) - step_time)


def _compare(r: Run, c, tr, key, losses: List[float], seen,
             stand_in: Optional[Dict] = None) -> None:
    import jax

    steps = tr["compared_steps"]
    ref_losses, ref_g1, ref_delta = reference_steps(c, tr, r.seed, steps)
    if stand_in is None:
        p0 = _params_again(c, key)
        got_delta = change_norms(lm.leaf_paths(p0), jax.tree.leaves(p0),
                                 jax.tree.leaves(seen["p"]))
        del p0
        got_g1 = seen["g1"]
    else:
        losses, got_g1, got_delta = reference_steps(c, tr, r.seed, steps,
                                                    **stand_in)
    loss_gap = max(abs(a - b) for a, b in zip(losses, ref_losses))
    skip = lm.small_leaves(ref_g1)
    g_gap, g_where = lm.worst_leaf_gap(got_g1, ref_g1, skip)
    d_gap, d_where = lm.worst_leaf_gap(got_delta, ref_delta, skip)
    r.note(stand_in=stand_in, losses=losses, reference_losses=ref_losses,
           grad_worst_leaf=g_where, change_worst_leaf=d_where,
           leaves_left_out=skip,
           per_leaf={k: {"grad": got_g1[k], "grad_ref": ref_g1[k],
                         "change": got_delta[k], "change_ref": ref_delta[k]}
                     for k in ref_g1})
    lim = tr["limits"]
    r.limits += [Limit("loss_gap", loss_gap, lim["loss_gap"]),
                 Limit("grad_norm_gap", g_gap, lim["grad_norm_gap"]),
                 Limit("change_norm_gap", d_gap, lim["change_norm_gap"])]


def _params_again(c, key):
    import functools

    import jax
    import jax.numpy as jnp

    ref = lm.reference_module(c)
    return jax.jit(functools.partial(ref.make_params, c,
                                     dtype=jnp.bfloat16))(key)


def reference_steps(c, tr, seed: int, steps: int, *,
                    quant: Optional[str] = None, rows: Optional[int] = None):
    """The plain reference's first ``steps`` adamw steps from the seeded
    weights on the same batches: per-step losses, the first gradient's and
    the parameters' change's per-leaf norms.

    As the configuration states, parameters are held in bfloat16 (each
    update is rounded to it, and a step too small for bfloat16 leaves a
    parameter where it was) and the optimizer's moments in float32; every
    product and gradient is computed in float32 from the parameters'
    values. Gradients are summed one row at
    a time; adamw's moments wait on the host between steps, so that the
    float32 state fits beside the model. ``rows`` keeps only the first rows
    of each batch (a planted fault)."""
    import jax
    import jax.numpy as jnp

    ref = lm.reference_module(c)
    opt = tr["optimizer"]
    data = TokenBatches(c["vocab_size"], tr["seq"], tr["global_batch"], seed)
    p = _params_again(c, harness.key_from_seed(seed))    # bfloat16
    paths = lm.leaf_paths(p)
    leaves0 = jax.device_get(jax.tree.leaves(p))

    @jax.jit
    def grad(p, tokens, labels):
        # from the bfloat16 values, everything in float32
        up = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        return jax.value_and_grad(
            lambda q: ref.sequence_loss(c, q, tokens, labels, quant))(up)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    scale = jax.jit(lambda a, k: jax.tree.map(lambda x: x / k, a),
                    donate_argnums=0)
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(x * x))
                               for x in jax.tree.leaves(t)])

    @functools.partial(jax.jit, donate_argnums=(0, 3))
    def adamw(g, m, v, p, t):
        b1, b2 = opt["b1"], opt["b2"]
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** t)
        vh = v / (1 - b2 ** t)
        p32 = p.astype(jnp.float32)
        delta = mh / (jnp.sqrt(vh) + opt["eps"]) + opt["weight_decay"] * p32
        # an output in bfloat16 is rounded: the parameters are stored so
        return m, v, (p32 - opt["lr"] * delta).astype(jnp.bfloat16)

    m = [np.zeros(x.shape, np.float32) for x in leaves0]
    v = [np.zeros(x.shape, np.float32) for x in leaves0]
    losses, g1 = [], None
    for step in range(steps):
        b = data.make(step)
        n = rows or b["tokens"].shape[0]
        acc, total = None, 0.0
        for row in range(n):
            loss, g = grad(p, b["tokens"][row], b["labels"][row])
            total += float(loss)
            acc = g if acc is None else add(acc, g)
        g = scale(acc, float(n))
        losses.append(total / n)
        if step == 0:
            g1 = dict(zip(paths, map(float, norms(g))))
        treedef = jax.tree.structure(p)
        gl, pl = jax.tree.leaves(g), jax.tree.leaves(p)
        del g, p
        new_p = []
        for k in range(len(gl)):
            mk, vk, pk = adamw(gl[k], m[k], v[k], pl[k],
                               jnp.float32(step + 1))
            gl[k] = pl[k] = None
            m[k], v[k] = np.asarray(mk), np.asarray(vk)
            new_p.append(pk)
        p = jax.tree.unflatten(treedef, new_p)
    return losses, g1, change_norms(paths, leaves0, jax.tree.leaves(p))


def change_norms(paths, before, after) -> Dict[str, float]:
    """Per-leaf norm of ``after - before`` in float32, one leaf on the
    device at a time."""
    import jax
    import jax.numpy as jnp

    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    return {k: float(norm(jnp.asarray(a), jnp.asarray(b)))
            for k, b, a in zip(paths, before, after)}
