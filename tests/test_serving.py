"""Continuous-batching serving engine + SLO co-scheduling tests (ISSUE 10).

Covers:

  * chunked prefill regression: ``greedy_generate`` is token-identical to
    the retired token-by-token loop (``greedy_generate_reference``) while
    issuing ~prompt_len/chunk fewer compiled calls;
  * per-request token identity: requests served through a shared
    continuous-batching engine (staggered arrivals, lane reuse, mid-run
    admit/evict) reproduce exactly the tokens of isolated single-request
    generation — including on a recurrent-state family, where a stale
    evicted lane would actually corrupt the successor request;
  * admit/evict lane invariants via ``audit_serving_engine``, and the audit
    firing on injected corruption (recompile, fingerprint drift, aliasing);
  * ``compile_count == 1`` across every batch occupancy the run visits;
  * continuous vs static batching: same trace, same compiled step, fewer
    engine calls (the perf headline, deterministically);
  * replay determinism of the seeded diurnal/bursty request stream;
  * the SLO -> sigmoid utility mapping (static in z — the sanitizer's
    exact-equality utility check depends on that — and front-loaded);
  * end-to-end co-scheduling: a serving burst reclaims workers from a
    training ring through the ordinary utility pricing and hands them
    back, with the backend's reported SLO attainment matching the event
    log (and the sanitizer catching a deliberate misreport).
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.analysis.sanitize import SanitizerError
from repro.cluster.topology import Link, Server, SubstrateGraph
from repro.configs import get_arch
from repro.core.problem import DDLJSInstance, Job
from repro.core.utility import sqrt_utility
from repro.launch.serve import (
    Request,
    ServingEngine,
    audit_serving_engine,
    greedy_generate,
    greedy_generate_reference,
    serve_requests,
)
from repro.models.model import build_model
from repro.sched import (
    DiurnalRequestStream,
    EmbeddingCommitted,
    OnlineDriver,
    RequestArrival,
    RequestCompletion,
    RequestFirstToken,
    RequestStreamConfig,
    ServeSLO,
    ServingBackend,
    make_serve_job,
    slo_attainment_from_events,
)


@pytest.fixture(scope="module")
def dense():
    cfg = get_arch("qwen3-0.6b").reduced()
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def hybrid():
    # zamba2: SSM + conv recurrent state — the family whose decode cache is
    # NOT self-masking, so evict-zeroing and the dtype fixed point actually
    # carry the test
    cfg = get_arch("zamba2-1.2b").reduced()
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _prompts(model, batch, length, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length),
                              0, model.cfg.vocab)


class TestChunkedPrefill:
    @pytest.mark.parametrize("fix", ["dense", "hybrid"])
    def test_token_identical_to_reference_loop(self, fix, request):
        model, params = request.getfixturevalue(fix)
        prompts = _prompts(model, 2, 9)
        out_ref = greedy_generate_reference(model, params, prompts, 6, 24)
        for chunk in (1, 4, 8):
            out = greedy_generate(model, params, prompts, 6, 24,
                                  prefill_chunk=chunk)
            np.testing.assert_array_equal(
                np.asarray(out), np.asarray(out_ref),
                err_msg=f"chunk={chunk} diverged from token-by-token loop")

    def test_zero_max_new(self, dense):
        model, params = dense
        prompts = _prompts(model, 1, 5)
        out = greedy_generate(model, params, prompts, 0, 16)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(prompts))


class TestContinuousBatching:
    @pytest.mark.parametrize("fix", ["dense", "hybrid"])
    def test_token_identity_with_lane_reuse(self, fix, request):
        """5 staggered requests on 3 lanes: every request's tokens equal
        isolated single-request generation — lane eviction/reuse and mixed
        batch compositions must never leak across requests."""
        model, params = request.getfixturevalue(fix)
        engine = ServingEngine(model, params, max_batch=3, max_seq=32,
                               prefill_chunk=4)
        rng = np.random.default_rng(3)
        reqs = [Request(id=i,
                        prompt=rng.integers(0, model.cfg.vocab, size=5 + i,
                                            dtype=np.int32),
                        max_new=6, arrival=3 * i)
                for i in range(5)]
        serve_requests(engine, reqs)
        assert len(engine.finished) == 5
        assert engine.compile_count == 1  # one decode trace, every occupancy
        assert audit_serving_engine(engine) == []
        for req in reqs:
            solo = greedy_generate(model, params,
                                   np.asarray(req.prompt)[None, :],
                                   req.max_new, 32, prefill_chunk=4)
            expect = np.asarray(solo)[0, len(req.prompt):]
            np.testing.assert_array_equal(
                np.asarray(req.tokens), expect,
                err_msg=f"request {req.id} diverged from solo generation")

    @pytest.mark.parametrize("fix", ["dense", "hybrid"])
    def test_decode_step_donates_the_cache(self, fix, request):
        """Every decode step hands its cache to the compiled step, which
        updates it in place: the arrays passed in are deleted once it
        returns, at every occupancy, and the step compiles once."""
        model, params = request.getfixturevalue(fix)
        engine = ServingEngine(model, params, max_batch=3, max_seq=32,
                               prefill_chunk=4)
        rng = np.random.default_rng(5)
        for i, max_new in enumerate((6, 8, 10)):
            engine.submit(Request(id=i, prompt=rng.integers(
                0, model.cfg.vocab, size=4 + i, dtype=np.int32),
                max_new=max_new))
        occupancies = set()
        while not engine.idle:
            engine.admit(limit=1)
            before = jax.tree.leaves(engine.cache)
            occupancies.add(int(engine.active.sum()))
            engine.step()
            assert all(x.is_deleted() for x in before)
            assert not any(x.is_deleted()
                           for x in jax.tree.leaves(engine.cache))
        assert occupancies == {1, 2, 3}
        assert engine.compile_count == 1
        assert audit_serving_engine(engine) == []

    def test_eos_retires_and_lane_is_reused(self, dense):
        model, params = dense
        engine = ServingEngine(model, params, max_batch=1, max_seq=32,
                               prefill_chunk=4)
        rng = np.random.default_rng(9)
        a = Request(id=0, prompt=rng.integers(0, model.cfg.vocab, size=6,
                                              dtype=np.int32), max_new=20)
        serve_requests(engine, [a], max_steps=4)
        # force-retire a by serving to completion, then run b on the lane
        serve_requests(engine, [])
        b = Request(id=1, prompt=rng.integers(0, model.cfg.vocab, size=6,
                                              dtype=np.int32), max_new=6)
        serve_requests(engine, [b])
        solo = greedy_generate(model, params, np.asarray(b.prompt)[None, :],
                               6, 32, prefill_chunk=4)
        np.testing.assert_array_equal(
            np.asarray(b.tokens), np.asarray(solo)[0, len(b.prompt):],
            err_msg="lane reuse leaked the predecessor's cache state")
        assert audit_serving_engine(engine) == []

    def test_continuous_beats_static_on_engine_calls(self, dense):
        """Same bursty trace, same compiled step: continuous batching
        finishes in strictly fewer engine calls (static idles lanes while
        draining). Deterministic — argmax decode, no wall-clock."""
        model, params = dense

        def trace():
            rng = np.random.default_rng(11)
            return [Request(id=i,
                            prompt=rng.integers(0, model.cfg.vocab, size=6,
                                                dtype=np.int32),
                            max_new=int(rng.integers(2, 13)),
                            arrival=(i // 3) * 6)
                    for i in range(9)]

        clocks = {}
        for static in (False, True):
            engine = ServingEngine(model, params, max_batch=3, max_seq=32,
                                   prefill_chunk=4)
            serve_requests(engine, trace(), static=static)
            assert len(engine.finished) == 9
            assert engine.compile_count == 1
            clocks[static] = engine.clock
        assert clocks[False] < clocks[True], (
            f"continuous used {clocks[False]} calls vs static "
            f"{clocks[True]} — admission policy made no difference")

    def test_audit_fires_on_corruption(self, dense):
        model, params = dense
        engine = ServingEngine(model, params, max_batch=2, max_seq=32,
                               prefill_chunk=4)
        rng = np.random.default_rng(1)
        serve_requests(engine, [
            Request(id=0, prompt=rng.integers(0, model.cfg.vocab, size=5,
                                              dtype=np.int32), max_new=4)])
        assert audit_serving_engine(engine) == []
        # recompile: decode traced more than once
        engine.compile_count = 2
        assert any("compile" in p for p in audit_serving_engine(engine))
        engine.compile_count = 1
        # closure drift: a static attr mutated after construction
        engine.max_seq = 64
        assert any("fingerprint" in p or "static" in p
                   for p in audit_serving_engine(engine))
        engine.max_seq = 32
        # lane aliasing: one request on two lanes
        req = engine.finished[0]
        engine.active[:] = True
        engine.positions[:] = 1
        engine.lane_req = [req, req]
        assert any("alias" in p for p in audit_serving_engine(engine))

    def test_prompt_too_long_rejected(self, dense):
        model, params = dense
        engine = ServingEngine(model, params, max_batch=1, max_seq=8,
                               prefill_chunk=4)
        with pytest.raises(ValueError, match="cannot fit"):
            engine.submit(Request(id=0, prompt=np.zeros(8, np.int32),
                                  max_new=2))


class TestRequestStream:
    def test_replay_determinism(self):
        cfg = RequestStreamConfig(job_id=7, base_rate=3.0, burst_prob=0.3,
                                  burst_size=5, seed=13)
        stream = DiurnalRequestStream(cfg)
        first = [stream.pre_slot(t) for t in range(20)]
        stream.reset()
        second = [stream.pre_slot(t) for t in range(20)]
        assert first == second  # frozen dataclasses: structural equality
        assert sum(len(evs) for evs in first) > 0
        ids = [e.request_id for evs in first for e in evs]
        assert ids == list(range(len(ids)))  # unique, dense, ordered

    def test_seed_changes_trace(self):
        a = DiurnalRequestStream(RequestStreamConfig(job_id=7, seed=13))
        b = DiurnalRequestStream(RequestStreamConfig(job_id=7, seed=14))
        assert [a.pre_slot(t) for t in range(20)] \
            != [b.pre_slot(t) for t in range(20)]

    def test_window_respected(self):
        stream = DiurnalRequestStream(RequestStreamConfig(
            job_id=1, start=5, end=8, base_rate=50.0, seed=0))
        for t in (0, 4, 8, 9):
            assert stream.pre_slot(t) == []
        assert any(stream.pre_slot(t) for t in (5, 6, 7))


class TestServeJobUtility:
    def test_static_in_z_and_front_loaded(self):
        slo = ServeSLO(ttft_slots=2, tpot_slots=1.0, weight=50.0)
        job = make_serve_job(3, arrival=0, offered_tokens=500.0, slo=slo,
                             tokens_per_worker_slot=32.0)
        # static function of z: the sanitizer's exact-equality utility-cache
        # check forbids backlog-dependent (dynamic) utilities
        assert job.utility(96.0) == job.utility(96.0)
        # front-loaded: marginal utility is high from the first token and
        # decays once the offered load has been served
        early = job.utility.marginal(0.0, 64.0)
        late = job.utility.marginal(2 * 500.0, 64.0)
        assert early > 0 and early > 10 * late
        # budget: Eq. (11) completes the job once the offered load is served
        assert job.worker_time_budget() == pytest.approx(500.0 / 32.0)

    def test_tighter_ttft_is_steeper(self):
        tight = make_serve_job(1, arrival=0, offered_tokens=500.0,
                               slo=ServeSLO(ttft_slots=1))
        loose = make_serve_job(2, arrival=0, offered_tokens=500.0,
                               slo=ServeSLO(ttft_slots=8))
        # steeper sigmoid = more of the utility concentrated in the
        # earliest tokens
        assert tight.utility.marginal(0.0, 32.0) \
            > loose.utility.marginal(0.0, 32.0)

    def test_attainment_formula(self):
        slo = ServeSLO(ttft_slots=2, tpot_slots=1.0)
        events = [
            RequestArrival(0, 1, 0),
            RequestCompletion(3, 1, 0, n_tokens=4, ttft_slots=1,
                              decode_slots=3),   # met
            RequestCompletion(5, 1, 1, n_tokens=4, ttft_slots=4,
                              decode_slots=3),   # TTFT miss
            RequestCompletion(6, 2, 2, n_tokens=4, ttft_slots=1,
                              decode_slots=3),   # other job
        ]
        assert slo_attainment_from_events(events, 1, slo) == 0.5
        assert slo_attainment_from_events([], 1, slo) == 1.0
        # single-token completions have no decode phase: TPOT vacuous
        assert slo.met_by(0, 1, 0)


def _co_setup(dense_model, *, weight=80.0, horizon=16, burst_start=6):
    model, params = dense_model
    servers = [Server(i, 0, {"gpus": 2.0, "mem": 8.0}) for i in range(2)]
    links = []
    for s in servers:
        links += [Link(s.node, "r0", 100.0), Link("r0", s.node, 100.0)]
    graph = SubstrateGraph(servers, links, n_racks=1, n_core=0)
    train = Job(id=0, arrival=0, max_workers=4,
                demands={"gpus": 1.0, "mem": 1.0}, budgets={"gpus": 500.0},
                bandwidth=5.0, zeta=1.0, utility=sqrt_utility(4.0))
    slo = ServeSLO(ttft_slots=2, tpot_slots=1.0, weight=weight)
    serve = make_serve_job(1, arrival=burst_start, offered_tokens=800.0,
                           slo=slo, tokens_per_worker_slot=64.0,
                           max_workers=3, bandwidth=5.0)
    inst = DDLJSInstance(graph=graph, jobs=[train, serve], horizon=horizon)
    engine = ServingEngine(model, params, max_batch=4, max_seq=32,
                           prefill_chunk=4)
    stream = DiurnalRequestStream(RequestStreamConfig(
        job_id=1, start=burst_start, base_rate=2.0, burst_prob=0.6,
        burst_size=4, prompt_len=(4, 8), max_new=(3, 6), seed=7))
    backend = ServingBackend({1: engine}, tokens_per_worker_slot=64.0)
    return inst, stream, backend, engine, slo


class TestCoScheduling:
    def test_burst_reclaims_workers_and_returns_them(self, dense):
        horizon, burst_start = 16, 6
        inst, stream, backend, engine, slo = _co_setup(
            dense, horizon=horizon, burst_start=burst_start)
        res = OnlineDriver(inst, events=stream, backend=backend,
                           sanitize=True).run("gadget")
        per = {0: dict.fromkeys(range(horizon), 0),
               1: dict.fromkeys(range(horizon), 0)}
        for e in res.events:
            if isinstance(e, EmbeddingCommitted):
                per[e.job_id][e.t] += e.n_workers
        # before the burst training owns the cluster's 4 workers
        assert all(per[0][t] == 4 and per[1][t] == 0
                   for t in range(burst_start))
        # the burst reclaims workers from the training ring ...
        burst = range(burst_start, horizon)
        assert min(per[0][t] for t in burst) <= 2
        assert max(per[1][t] for t in burst) >= 2
        # ... and hands them back once the backlog clears
        assert per[0][horizon - 1] == 4
        # request lifecycle is in the log and internally consistent
        firsts = [e for e in res.events if isinstance(e, RequestFirstToken)]
        dones = [e for e in res.events if isinstance(e, RequestCompletion)]
        assert firsts and dones
        assert all(e.ttft_slots >= 0 for e in firsts)
        # backend-reported attainment == log-derived (the sanitizer already
        # asserted this every slot; pin the final value here too)
        att = slo_attainment_from_events(res.events, 1, slo)
        assert backend.reports[-1]["slo_attainment"] == att
        assert engine.compile_count == 1

    def test_replay_bit_identical(self, dense):
        """Same seeds, fresh engine/backend: the co-scheduled run replays
        to the identical event log and worker-time accounting."""
        runs = []
        for _ in range(2):
            inst, stream, backend, engine, slo = _co_setup(dense)
            res = OnlineDriver(inst, events=stream,
                               backend=backend).run("gadget")
            runs.append((res.events, dict(res.state.z)))
        assert runs[0][0] == runs[1][0]
        assert runs[0][1] == runs[1][1]

    def test_sanitizer_catches_attainment_misreport(self, dense):
        inst, stream, backend, engine, slo = _co_setup(dense)

        class Misreporting:
            name = "misreporting"

            def execute_slot(self, decision, execution):
                out = backend.execute_slot(decision, execution)
                for row in out.measured.values():
                    if "slo_attainment" in row:
                        row["slo_attainment"] = 0.123  # lie about the SLO
                return out

        with pytest.raises(SanitizerError, match="slo_attainment"):
            OnlineDriver(inst, events=stream, backend=Misreporting(),
                         sanitize=True).run("gadget")

    def test_training_only_fleet_unaffected(self, dense):
        """A ServingBackend with no serve embeddings delegates everything to
        the inner backend: pure-training runs are bit-identical to the
        default AnalyticBackend path (the fig4 safety property)."""
        servers = [Server(i, 0, {"gpus": 2.0, "mem": 8.0}) for i in range(2)]
        links = []
        for s in servers:
            links += [Link(s.node, "r0", 100.0), Link("r0", s.node, 100.0)]
        graph = SubstrateGraph(servers, links, n_racks=1, n_core=0)
        jobs = [Job(id=i, arrival=i, max_workers=3,
                    demands={"gpus": 1.0, "mem": 1.0},
                    budgets={"gpus": 30.0}, bandwidth=5.0, zeta=1.0,
                    utility=sqrt_utility(2.0 + i)) for i in range(3)]
        inst = DDLJSInstance(graph=graph, jobs=jobs, horizon=10)
        base = OnlineDriver(inst).run("gadget")
        served = OnlineDriver(inst, backend=ServingBackend({})).run("gadget")
        assert base.events == served.events
        assert dict(base.state.z) == dict(served.state.z)
        assert [dataclasses.asdict(r) for r in base.records] \
            == [dataclasses.asdict(r) for r in served.records]
