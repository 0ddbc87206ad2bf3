"""repro.analysis tests (ISSUE 7).

Covers the three analysis layers and their acceptance criteria:

  * lint rules fire on synthetic fixture trees (one per rule) and stay
    quiet on the equivalent compliant code;
  * the real repo is lint-clean against the checked-in baseline (no new,
    no stale, no malformed entries) — the CI gate, run as a test;
  * baseline mechanics: suppression by key, stale detection, justification
    required;
  * the sanitizer is invisible when the accounting is correct — a
    ``sanitize=True`` run returns a bit-identical ``SimResult`` — and
    raises on injected corruption (a skipped utility-cache refresh, an
    out-of-range progress factor) that the default path silently accepts;
  * ``REPRO_SANITIZE`` enablement semantics;
  * the kernel checker accepts the known-good quant_ring configurations
    and rejects a non-dividing rows override and a block that overflows
    the tile budget (the gap ``_rows_per_tile`` itself does not police).

ISSUE 8 additions: ``--write-baseline`` placeholders must keep failing the
gate until replaced (the bulk-silencing fix, pinned), ``--json`` findings
output, and the sanitizer over the **live** execution path — a
``REPRO_SANITIZE=1`` LiveBackend run stays bit-identical, still catches
injected utility-cache corruption, and the compiled-step cache audit
(`audit_compiled_step_cache`) raises on a mutated ``RingWorkerGroup``.
The collective verifier itself is covered in
``tests/test_collectives_verifier.py``.
"""

import dataclasses
import json
import os
import textwrap

import numpy as np
import pytest

from repro.analysis import SanitizerError, SlotSanitizer, sanitize_enabled
from repro.analysis import kernels as akern
from repro.analysis import lint as alint
from repro.cluster import make_fat_tree
from repro.cluster.topology import Embedding, Link, Server, SubstrateGraph
from repro.cluster.trace import JobTraceConfig, generate_jobs
from repro.core.problem import DDLJSInstance, Job, ScheduleState
from repro.core.rar_model import RarJobProfile
from repro.core.utility import sqrt_utility
from repro.kernels.quant_ring import _TILE_BUDGET_BYTES, _rows_per_tile
from repro.sched import (
    ContentionConfig,
    LiveBackend,
    OnlineDriver,
    SchedulerBase,
    SlotDecision,
    registry,
)
from repro.sched.backend import SlotOutcome


# ---------------------------------------------------------------------------
# lint fixtures
# ---------------------------------------------------------------------------

def _write_tree(root, files):
    for rel, src in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return str(root)


def _rules_fired(violations):
    return {v.rule for v in violations}


def test_lint_wallclock_fires_in_decision_paths_only(tmp_path):
    root = _write_tree(tmp_path, {
        "sched/bad.py": """
            import time

            def decide():
                return time.time()
        """,
        "util/ok.py": """
            import time

            def bench():
                return time.perf_counter()
        """,
    })
    vs = alint.run_lint(root)
    assert [v.key for v in vs] == ["wallclock:sched/bad.py:decide"]


def test_lint_unseeded_rng_fires_anywhere(tmp_path):
    root = _write_tree(tmp_path, {
        "util/rng.py": """
            import random
            import numpy as np

            def bad():
                return np.random.rand(3) + random.random()

            def good(seed):
                rng = np.random.default_rng(seed)
                return rng.standard_normal(3)
        """,
    })
    vs = alint.run_lint(root)
    assert _rules_fired(vs) == {"unseeded-rng"}
    assert len(vs) == 2  # np.random.rand and random.random, not default_rng
    assert all(v.symbol == "bad" for v in vs)


def test_lint_unordered_iter_tracks_set_typed_locals(tmp_path):
    root = _write_tree(tmp_path, {
        "core/order.py": """
            def bad(xs):
                pending = set(xs)
                return [x for x in pending]

            def bad_literal(a, b):
                for x in {a} | {b}:
                    yield x

            def good(xs):
                pending = set(xs)
                return [x for x in sorted(pending)]
        """,
    })
    vs = alint.run_lint(root)
    assert sorted(v.key for v in vs) == [
        "unordered-iter:core/order.py:bad",
        "unordered-iter:core/order.py:bad_literal",
    ]


def test_lint_unfrozen_dataclass_scoped_to_sched_api(tmp_path):
    src = """
        import dataclasses

        @dataclasses.dataclass
        class Record:
            x: int

        @dataclasses.dataclass(frozen=True)
        class Frozen:
            x: int

        @dataclasses.dataclass
        class _Private:
            x: int
    """
    root = _write_tree(tmp_path, {"sched/api.py": src, "util/other.py": src})
    vs = alint.run_lint(root)
    assert [v.key for v in vs] == ["unfrozen-dataclass:sched/api.py:Record"]


def test_lint_mutable_default(tmp_path):
    root = _write_tree(tmp_path, {
        "util/defs.py": """
            def bad(acc=[]):
                return acc

            def good(acc=None):
                return acc or []
        """,
    })
    vs = alint.run_lint(root)
    assert [v.key for v in vs] == ["mutable-default:util/defs.py:bad"]


def test_lint_event_coverage_transitive_subclasses(tmp_path):
    root = _write_tree(tmp_path, {
        "sched/events.py": """
            class ClusterEvent:
                pass

            class Alpha(ClusterEvent):
                pass

            class Beta(Alpha):
                pass
        """,
        "sched/driver.py": """
            from repro.sched.events import Alpha

            class OnlineDriver:
                def run(self, ev):
                    if isinstance(ev, Alpha):
                        return 1
                    return 0
        """,
    })
    vs = [v for v in alint.run_lint(root) if v.rule == "event-coverage"]
    # Beta (transitive subclass) is never referenced; the bare import of
    # Alpha does not count — the isinstance dispatch does
    assert [v.symbol for v in vs] == ["OnlineDriver.run[Beta]"]


def test_repo_is_lint_clean_against_baseline():
    """The CI gate as a test: no new violations, no stale/malformed entries."""
    violations = alint.run_lint()
    baseline = alint.Baseline.load(alint.default_baseline_path())
    new, stale = alint.apply_baseline(violations, baseline)
    assert new == [], "\n".join(str(v) for v in new)
    assert stale == []
    assert baseline.malformed == []


def test_lint_main_exit_codes(tmp_path):
    assert alint.main([]) == 0  # the real repo against the real baseline

    root = _write_tree(tmp_path, {
        "sched/bad.py": """
            import time

            def decide():
                return time.time()
        """,
    })
    empty = tmp_path / "empty_baseline.txt"
    empty.write_text("# empty\n")
    assert alint.main(["--root", root, "--baseline", str(empty)]) == 1

    ok = tmp_path / "baseline.txt"
    ok.write_text("wallclock:sched/bad.py:decide  # fixture debt\n")
    assert alint.main(["--root", root, "--baseline", str(ok)]) == 0

    # paid-off debt must leave the ledger: same baseline, violation gone
    (tmp_path / "sched" / "bad.py").write_text("def decide():\n    return 0\n")
    assert alint.main(["--root", root, "--baseline", str(ok)]) == 1


def test_baseline_requires_justification(tmp_path):
    path = tmp_path / "baseline.txt"
    path.write_text("wallclock:sched/bad.py:decide\n")
    baseline = alint.Baseline.load(str(path))
    assert baseline.entries == {}
    assert baseline.malformed == ["wallclock:sched/bad.py:decide"]


# ---------------------------------------------------------------------------
# sanitizer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def instance():
    graph = make_fat_tree(n_servers=8, seed=1)
    jobs = generate_jobs(JobTraceConfig(n_jobs=10, horizon=12, seed=2))
    return DDLJSInstance(graph=graph, jobs=jobs, horizon=12)


def _run(inst, *, sanitize=None, contention=None):
    driver = OnlineDriver(inst, sanitize=sanitize, contention=contention)
    return driver.run(registry.create("fifo", seed=0))


def test_sanitized_run_is_bit_identical(instance, monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    plain = _run(instance, sanitize=False)
    checked = _run(instance, sanitize=True)
    assert checked.records == plain.records  # frozen dataclasses: == is deep
    assert checked.completion_slot == plain.completion_slot
    assert checked.state.z == plain.state.z
    assert checked.total_utility == plain.total_utility
    assert len(checked.events) == len(plain.events)


def test_sanitized_run_passes_under_contention(instance):
    res = _run(instance, sanitize=True,
               contention=ContentionConfig(oversubscription=2.0))
    assert res.records  # ran to completion with every invariant re-derived


def test_sanitizer_catches_skipped_utility_refresh(instance, monkeypatch):
    """The injected corruption: commit_slot skips the utility-cache refresh.
    The default path must stay silent (that is the bug class — silently
    stale totals); sanitize=True must raise."""
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    monkeypatch.setattr(ScheduleState, "_test_skip_utility_refresh", True)
    silent = _run(instance, sanitize=None)   # default: no sanitizer
    assert silent.records, "default path must not detect the corruption"
    with pytest.raises(SanitizerError, match="cached utility"):
        _run(instance, sanitize=True)


def test_sanitizer_catches_out_of_range_factor(instance, monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)

    class OverCreditBackend:
        name = "over-credit"

        def execute_slot(self, decision, ex):
            return SlotOutcome(factors=[1.5] * len(decision.embeddings))

    def run(sanitize):
        driver = OnlineDriver(instance, backend=OverCreditBackend(),
                              sanitize=sanitize)
        return driver.run(registry.create("fifo", seed=0))

    run(False)  # default path accepts the bogus credit silently
    with pytest.raises(SanitizerError, match="progress factor"):
        run(True)


def test_sanitize_enabled_env_semantics(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    assert sanitize_enabled() is False
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    assert sanitize_enabled() is True
    assert sanitize_enabled(explicit=False) is False  # explicit wins
    monkeypatch.setenv("REPRO_SANITIZE", "0")
    assert sanitize_enabled() is False


def test_wire_formula_check_agrees_for_fused_profiles():
    prof = RarJobProfile(d=1 << 20, bandwidth=1e9, reduce_speed=1e10,
                         t_fwd_per_sample=1e-4, t_bwd=1e-2, batch_size=32,
                         compression="int8-fused")
    job = dataclasses.make_dataclass("J", ["id", "profile"])(0, prof)
    SlotSanitizer()._check_wire_formulas(job)  # must not raise


# ---------------------------------------------------------------------------
# kernel checker
# ---------------------------------------------------------------------------

def test_kernel_checker_accepts_known_good_configs():
    for spec in (akern.KernelSpec(64, 4096),
                 akern.KernelSpec(512, 256, kernel="dequant_add_quantize",
                                  rows_per_tile=128),
                 akern.KernelSpec(7, 4096, kernel="dequant_accumulate")):
        result = akern.check_spec(spec)
        assert result.ok, result.errors
        assert result.tile_bytes <= _TILE_BUDGET_BYTES


def test_kernel_checker_rejects_non_dividing_rows():
    result = akern.check_spec(akern.KernelSpec(48, 512, rows_per_tile=5))
    assert not result.ok
    assert "must divide" in result.errors[0]


def test_kernel_checker_rejects_tile_budget_overflow():
    # the gap the checker closes: _rows_per_tile resolves this to the whole
    # 4-row dimension (the only tile height under 32 the TPU accepts)
    # without complaint, but that tile already overflows the budget
    assert _rows_per_tile(4, 1 << 20, None, 5) == 4
    result = akern.check_spec(akern.KernelSpec(4, 1 << 20))
    assert not result.ok
    assert any("_TILE_BUDGET_BYTES" in e for e in result.errors)


def test_kernel_checker_matches_real_tiling():
    """The checker's byte table must reproduce the tiling quant_ring picks."""
    for kernel, bpe in akern.BYTES_PER_ELEM.items():
        spec = akern.KernelSpec(96, 2048, kernel=kernel)
        assert akern.check_spec(spec).rows == _rows_per_tile(
            96, 2048, None, bytes_per_elem=bpe)


def test_kernel_checker_cli_suite():
    assert akern.main([]) == 0
    suite = akern.default_suite()
    assert sum(1 for _, ok in suite if ok) >= 3
    assert sum(1 for _, ok in suite if not ok) >= 1
    assert akern.main(["--check", "48,512,quantize_pack,5"]) == 1


def test_sanitize_env_integration(instance, monkeypatch):
    """REPRO_SANITIZE=1 routes through the driver constructor default."""
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    assert OnlineDriver(instance).sanitize is True
    monkeypatch.delenv("REPRO_SANITIZE")
    assert OnlineDriver(instance).sanitize is False


# ---------------------------------------------------------------------------
# shared baseline plumbing: --write-baseline placeholders cannot silence
# ---------------------------------------------------------------------------

_WALLCLOCK_FIXTURE = {
    "sched/bad.py": """
        import time

        def decide():
            return time.time()
    """,
}


def test_write_baseline_placeholders_cannot_silence_lint(tmp_path):
    """The pinning test for the --write-baseline fix: a freshly
    bootstrapped baseline documents the debt but still fails the gate
    until every `TODO justify` placeholder is replaced."""
    root = _write_tree(tmp_path, _WALLCLOCK_FIXTURE)
    baseline = tmp_path / "baseline.txt"
    assert alint.main(["--root", root, "--baseline", str(baseline),
                       "--write-baseline"]) == 0
    text = baseline.read_text()
    assert "wallclock:sched/bad.py:decide  # TODO justify" in text

    # the regression this pins: written placeholders used to satisfy the
    # justification requirement and pass; they must fail as malformed
    assert alint.main(["--root", root, "--baseline", str(baseline)]) == 1
    loaded = alint.Baseline.load(str(baseline))
    assert loaded.entries == {}
    assert loaded.malformed == ["wallclock:sched/bad.py:decide"
                                "  # TODO justify"]

    # a human-supplied justification is what flips it to green
    baseline.write_text(text.replace("TODO justify",
                                     "fixture debt, tracked"))
    assert alint.main(["--root", root, "--baseline", str(baseline)]) == 0


def test_lint_json_findings(tmp_path):
    root = _write_tree(tmp_path, _WALLCLOCK_FIXTURE)
    empty = tmp_path / "empty_baseline.txt"
    empty.write_text("# empty\n")
    out = tmp_path / "lint.json"
    rc = alint.main(["--root", root, "--baseline", str(empty),
                     "--json", str(out)])
    assert rc == 1
    data = json.loads(out.read_text())
    assert data["tool"] == "repro.analysis.lint"
    assert data["stale"] == [] and data["malformed"] == []
    (record,) = data["findings"]
    assert record["rule"] == "wallclock"
    assert record["path"] == "sched/bad.py"
    assert record["symbol"] == "decide"
    assert record["line"] > 0
    assert record["baselined"] is False
    assert record["key"] == "wallclock:sched/bad.py:decide"


def test_lint_json_marks_suppressed_findings(tmp_path):
    root = _write_tree(tmp_path, _WALLCLOCK_FIXTURE)
    ok = tmp_path / "baseline.txt"
    ok.write_text("wallclock:sched/bad.py:decide  # fixture debt\n")
    out = tmp_path / "lint.json"
    assert alint.main(["--root", root, "--baseline", str(ok),
                       "--json", str(out)]) == 0
    (record,) = json.loads(out.read_text())["findings"]
    assert record["baselined"] is True


# ---------------------------------------------------------------------------
# the sanitizer under LiveBackend (satellite 3): live execution stays
# bit-identical and the audit catches injected corruption
# ---------------------------------------------------------------------------

def _live_instance(horizon=3):
    servers = [Server(0, 0, {"gpus": 4.0}), Server(1, 0, {"gpus": 4.0})]
    links = []
    for s in servers:
        links.append(Link(s.node, "r0", 100.0))
        links.append(Link("r0", s.node, 100.0))
    graph = SubstrateGraph(servers, links, n_racks=1, n_core=0)
    job = Job(id=0, arrival=0, max_workers=2, demands={"gpus": 1.0},
              budgets={"gpus": 8.0}, bandwidth=1.0, zeta=1.0,
              utility=sqrt_utility(1.0))
    return DDLJSInstance(graph=graph, jobs=[job], horizon=horizon)


class _ColocTwo(SchedulerBase):
    """Places a colocated 2-worker ring for job 0 whenever active."""

    name = "coloc2-analysis"

    def decide(self, ctx):
        embeddings = []
        for job in ctx.active_jobs():
            emb = Embedding(job.id, [(0, 2)], [], job.bandwidth)
            if ctx.res.feasible(emb, job.demands):
                ctx.res.commit(emb, job.demands)
                embeddings.append(emb)
        return SlotDecision(ctx.t, embeddings, 0.0, 0.0,
                            len(ctx.active_jobs()), len(embeddings))


class _StubTrainer:
    """Duck-typed ElasticTrainer: replays the run_slot contract."""

    def __init__(self):
        self.params = {"w": np.zeros(16, np.float32)}
        self.step = 0

    def run_slot(self, plan):
        self.step += plan.steps
        return {"steps": plan.steps, "loss": 1.0, "workers": plan.workers,
                "worker_steps": plan.steps * plan.workers, "timings": {},
                "re_rings": 0}

    def restore(self):
        return True


def _live_run(monkeypatch, *, env=None, audit_cache=None, group=None):
    if env is None:
        monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    else:
        monkeypatch.setenv("REPRO_SANITIZE", env)
    trainer = _StubTrainer()
    if group is not None:
        trainer.group = group
    backend = LiveBackend({0: trainer}, steps_per_slot=4, calibrate=False,
                          audit_cache=audit_cache)
    driver = OnlineDriver(_live_instance(), backend=backend)
    return driver.run(_ColocTwo()), backend


def test_live_backend_sanitized_run_is_bit_identical(monkeypatch):
    """REPRO_SANITIZE=1 over the live execution path (driver sanitizer +
    the compiled-step cache audit) must not perturb any accounting."""
    plain, _ = _live_run(monkeypatch)
    checked, backend = _live_run(monkeypatch, env="1")
    assert backend.audit_cache is True
    assert checked.records == plain.records
    assert checked.state.z == plain.state.z
    assert checked.total_utility == plain.total_utility
    assert [r["factor"] for r in backend.reports] == [
        pytest.approx(1.0)] * len(backend.reports)


def test_live_backend_sanitizer_catches_utility_cache_corruption(
        monkeypatch):
    """The injected corruption from the sim tests, now under LiveBackend:
    the default live path silently accepts it, REPRO_SANITIZE=1 raises."""
    monkeypatch.setattr(ScheduleState, "_test_skip_utility_refresh", True)
    silent, _ = _live_run(monkeypatch)
    assert silent.records, "default live path must not detect corruption"
    with pytest.raises(SanitizerError, match="cached utility"):
        _live_run(monkeypatch, env="1")


def test_live_backend_audit_catches_mutated_group(monkeypatch):
    """A trainer whose RingWorkerGroup mutated a closed-over static attr:
    unaudited runs serve the stale executable, audited runs raise."""
    from repro.training.elastic import RingWorkerGroup
    from repro.training.optimizer import make_optimizer

    class _TinyModel:
        def init(self, key, dtype=None):
            return {"w": np.zeros(4, np.float32)}

        def loss(self, params, batch):
            return 0.0

    def mutated_group():
        group = RingWorkerGroup(_TinyModel(), make_optimizer("sgdm"),
                                global_batch=8, lr=1e-2, mode="ring")
        group.lr = 5e-3  # the hazard audit_compiled_step_cache detects
        return group

    silent, _ = _live_run(monkeypatch, group=mutated_group())
    assert silent.records, "unaudited path must not detect the mutation"
    with pytest.raises(SanitizerError, match="cache audit failed"):
        _live_run(monkeypatch, env="1", group=mutated_group())


def test_live_backend_audit_cache_explicit_overrides_env(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    backend = LiveBackend({}, audit_cache=False)
    assert backend.audit_cache is False
    monkeypatch.delenv("REPRO_SANITIZE")
    assert LiveBackend({}, audit_cache=True).audit_cache is True
