"""Host spans and work counters of the program's layers, read back from a
recorded ``jax.profiler`` trace on the CPU.

  * the slot loop: ``OnlineDriver`` with GADGET drives a reduced model's
    ``ElasticTrainer`` through ``LiveBackend``: each ``repro.*`` span of the
    slot, the decision, the backend and the trainer appears once per slot
    or step, nested under the one that caused it, with its ids;
  * the trainer's mid-slot re-ring and checkpoint restore;
  * the serving engine: one ``repro.serve.prefill`` per admitted request,
    one ``repro.serve.step`` per decode step with its live lanes, and the
    exact counters ``admit_time``, ``prefill_chunks``,
    ``prefill_padded_tokens`` and ``decode_lane_steps``;
  * a traced run computes what an untraced one does: the same losses and
    the same served tokens.
"""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.cluster import make_fat_tree
from repro.configs import get_arch
from repro.core.problem import DDLJSInstance, Job
from repro.core.rar_model import profile_from_arch
from repro.core.utility import sqrt_utility
from repro.data.pipeline import SyntheticTokens
from repro.launch.serve import Request, ServingEngine, serve_requests
from repro.models.model import build_model
from repro.sched import (
    LiveBackend,
    OnlineDriver,
    ScriptedEventStream,
    WorkerLeave,
    registry,
)
from repro.training.elastic import ElasticTrainer, SlotPlan
from repro.training.optimizer import make_optimizer

SEQ, BATCH, STEPS_PER_SLOT = 16, 2, 2


def _traced(fn, log_dir):
    """``fn()`` under the profiler; its result and the trace's ``repro.*``
    host spans as ``(name, start_ns, end_ns, ids)``, ordered by start."""
    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.append((e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns),
                                  dict(e.stats)))
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _parent(spans, k):
    """Name of the innermost span around span ``k``, or None."""
    _, s, e, _ = spans[k]
    around = [(e1 - s1, n) for j, (n, s1, e1, _) in enumerate(spans)
              if j != k and s1 <= s and e <= e1]
    return min(around)[1] if around else None


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


def _check_parents(spans, expect):
    for k, sp in enumerate(spans):
        if sp[0] in expect:
            assert _parent(spans, k) == expect[sp[0]], (sp, _parent(spans, k))


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_arch("qwen3-0.6b").reduced()
    return cfg, build_model(cfg)


# ---------------------------------------------------------------------------
# the slot loop: OnlineDriver -> GADGET -> LiveBackend -> ElasticTrainer
# ---------------------------------------------------------------------------

def _one_job_instance(n_params: int, horizon: int) -> DDLJSInstance:
    job = Job(id=0, arrival=0, max_workers=1,
              demands={"gpus": 1.0, "mem": 1.0}, budgets={"gpus": 1e9},
              bandwidth=1e9, zeta=1.0, utility=sqrt_utility(10.0),
              profile=profile_from_arch(n_params=float(n_params),
                                        tokens_per_batch=float(BATCH * SEQ)))
    graph = make_fat_tree(n_servers=1, n_racks=1, n_core=1,
                          gpus_choices=(1,), seed=0)
    return DDLJSInstance(graph=graph, jobs=[job], horizon=horizon)


def _drive(small_lm, ckpt_dir):
    """Three slots of GADGET over one job: two train, and in the third the
    whole ring leaves mid-slot, so the backend restores the checkpoint."""
    cfg, model = small_lm
    trainer = ElasticTrainer(model, make_optimizer("sgdm"),
                             SyntheticTokens(cfg.vocab, SEQ, BATCH, seed=0),
                             global_batch=BATCH, base_lr=1e-2, mode="ring",
                             checkpoint_dir=str(ckpt_dir))
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    driver = OnlineDriver(
        _one_job_instance(n_params, horizon=3),
        events=ScriptedEventStream(mid=[WorkerLeave(2, job_id=0, n=1)]),
        backend=LiveBackend({0: trainer}, steps_per_slot=STEPS_PER_SLOT))
    res = driver.run(registry.create("gadget", seed=0))
    return trainer, res


@pytest.fixture(scope="module")
def driven(small_lm, tmp_path_factory):
    base = tmp_path_factory.mktemp("slots")
    (trainer, res), spans = _traced(
        lambda: _drive(small_lm, base / "ckpt_traced"), base / "trace")
    plain, _ = _drive(small_lm, base / "ckpt_plain")
    return trainer, res, spans, plain


def test_slot_loop_spans_nest_under_their_cause(driven):
    _, _, spans, _ = driven
    _check_parents(spans, {
        "repro.slot": None,
        "repro.sched.decide": "repro.slot",
        "repro.gadget.candidates": "repro.sched.decide",
        "repro.gadget.lp": "repro.sched.decide",
        "repro.gadget.round": "repro.sched.decide",
        "repro.gadget.repair": "repro.sched.decide",
        "repro.backend.execute": "repro.slot",
        "repro.backend.calibrate": "repro.backend.execute",
        "repro.train.slot": "repro.backend.execute",
        "repro.train.form": "repro.train.slot",
        "repro.train.step": "repro.train.slot",
        "repro.train.input": "repro.train.step",
        "repro.train.dispatch": "repro.train.step",
        "repro.train.sync": "repro.train.step",
        "repro.train.checkpoint": "repro.train.slot",
        "repro.train.restore": "repro.backend.execute",
        "repro.slot.commit": "repro.slot",
    })
    counts = {}
    for name, *_ in spans:
        counts[name] = counts.get(name, 0) + 1
    assert counts == {
        "repro.slot": 3, "repro.sched.decide": 3,
        "repro.gadget.candidates": 3, "repro.gadget.lp": 3,
        "repro.gadget.round": 3, "repro.gadget.repair": 3,
        "repro.backend.execute": 3, "repro.slot.commit": 3,
        "repro.train.slot": 2, "repro.train.form": 2,
        "repro.backend.calibrate": 2, "repro.train.checkpoint": 2,
        "repro.train.step": 4, "repro.train.input": 4,
        "repro.train.dispatch": 4, "repro.train.sync": 4,
        "repro.train.restore": 1,
    }, counts


def test_slot_loop_span_ids(driven):
    trainer, _, spans, _ = driven
    for name in ("repro.slot", "repro.sched.decide", "repro.backend.execute",
                 "repro.slot.commit"):
        assert [sp[3]["t"] for sp in _named(spans, name)] == [0, 1, 2]
    assert [sp[3] for sp in _named(spans, "repro.train.slot")] == [
        {"job": 0, "workers": 1, "steps": STEPS_PER_SLOT}] * 2
    steps = _named(spans, "repro.train.step")
    assert [sp[3]["step"] for sp in steps] == list(range(trainer.step))
    assert all(sp[3]["workers"] == 1 for sp in steps)
    for sp in _named(spans, "repro.gadget.lp"):
        assert sp[3]["jobs"] == 1 and sp[3]["candidates"] >= 1


def test_slot_loop_steps_and_restore(driven):
    trainer, res, spans, _ = driven
    # one span per step the trainer took, and the third slot restored
    assert trainer.step == len(_named(spans, "repro.train.step"))
    assert trainer.restores == 1
    assert res.records[2].effective_worker_time == 0.0
    (restore,) = _named(spans, "repro.train.restore")
    (third,) = [sp for sp in _named(spans, "repro.slot") if sp[3]["t"] == 2]
    assert third[1] <= restore[1] and restore[2] <= third[2]


def test_losses_identical_with_and_without_profiler(driven):
    trainer, res, _, plain = driven
    assert len(trainer.losses) == 2 * STEPS_PER_SLOT
    assert trainer.losses == plain.losses
    assert trainer.step == plain.step


def test_mid_slot_re_ring_span(small_lm, tmp_path):
    cfg, model = small_lm
    trainer = ElasticTrainer(model, make_optimizer("sgdm"),
                             SyntheticTokens(cfg.vocab, SEQ, BATCH, seed=0),
                             global_batch=BATCH, base_lr=1e-2, mode="psum")
    out, spans = _traced(
        lambda: trainer.run_slot(SlotPlan(workers=1, steps=3, leave=(1, 1))),
        tmp_path)
    assert out["re_rings"] == 1
    (re_ring,) = _named(spans, "repro.train.re_ring")
    assert re_ring[3] == {"workers": 1}
    steps = _named(spans, "repro.train.step")
    assert [sp[3]["step"] for sp in steps] == [0, 1, 2]
    # the ring is re-formed after the first step and before the second
    assert steps[0][2] <= re_ring[1] and re_ring[2] <= steps[1][1]


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------

PROMPTS = (5, 6, 9, 3)
CHUNK = 4


def _serve(small_lm, params):
    _, model = small_lm
    engine = ServingEngine(model, params, max_batch=2, max_seq=32,
                           prefill_chunk=CHUNK)
    rng = np.random.default_rng(7)
    reqs = [Request(id=i, prompt=rng.integers(0, model.cfg.vocab, size=n,
                                              dtype=np.int32),
                    max_new=4 + i, arrival=2 * i)
            for i, n in enumerate(PROMPTS)]
    serve_requests(engine, reqs)
    return engine, reqs


@pytest.fixture(scope="module")
def served(small_lm, tmp_path_factory):
    _, model = small_lm
    params = model.init(jax.random.PRNGKey(0))
    (engine, reqs), spans = _traced(lambda: _serve(small_lm, params),
                                    tmp_path_factory.mktemp("serve"))
    _, plain = _serve(small_lm, params)
    return engine, reqs, spans, plain


def test_serving_spans_nest_and_count(served):
    engine, reqs, spans, _ = served
    _check_parents(spans, {
        "repro.serve.admit": None,
        "repro.serve.prefill": "repro.serve.admit",
        "repro.serve.step": None,
        "repro.serve.step.dispatch": "repro.serve.step",
        "repro.serve.step.readback": "repro.serve.step",
        "repro.serve.step.lanes": "repro.serve.step",
    })
    prefills = _named(spans, "repro.serve.prefill")
    assert sorted(sp[3]["req"] for sp in prefills) == [q.id for q in reqs]
    for sp in prefills:
        n = PROMPTS[sp[3]["req"]]
        assert sp[3] == {"req": sp[3]["req"], "tokens": n,
                         "chunks": -(-n // CHUNK)}
    steps = _named(spans, "repro.serve.step")
    assert len(steps) == engine.decode_steps > 0
    for child in ("dispatch", "readback", "lanes"):
        assert len(_named(spans, "repro.serve.step." + child)) == len(steps)
    assert all(1 <= sp[3]["lanes"] <= engine.max_batch for sp in steps)


def test_serving_counters_are_exact(served):
    engine, reqs, spans, _ = served
    for q in reqs:
        assert q.submit_time <= q.admit_time <= q.first_token_time
    # every decode step gives each live lane one token; prefill the first
    lane_steps = sum(sp[3]["lanes"] for sp in _named(spans,
                                                     "repro.serve.step"))
    assert engine.decode_lane_steps == lane_steps
    assert engine.decode_lane_steps == sum(len(q.tokens) - 1 for q in reqs)
    assert engine.prefill_chunks == sum(-(-n // CHUNK) for n in PROMPTS)
    assert engine.prefill_padded_tokens == sum(
        -(-n // CHUNK) * CHUNK - n for n in PROMPTS)


def test_served_tokens_identical_with_and_without_profiler(served):
    _, reqs, _, plain = served
    assert [q.tokens for q in reqs] == [q.tokens for q in plain]
    assert all(len(q.tokens) == q.max_new for q in reqs)
