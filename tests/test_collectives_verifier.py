"""repro.analysis.collectives tests (ISSUE 8).

Covers the jaxpr-level collective verifier and its acceptance criteria:

  * the repo sweep is clean — every registered ring variant and every
    ``make_ring_train_step`` mode passes all four axes at >= 3 world sizes
    (the CI gate, run as a test);
  * the seeded mutation suite: each axis demonstrably fails on its
    deliberately broken jaxpr (wrong permutation, mixed direction,
    branch-nested collective, byte-count drift vs rar_model, trailer-layout
    mismatch, cache-key-defeating weak type);
  * recompile-hazard audits: the ``STATIC_CLOSURE_ATTRS`` AST check fires
    on a post-``__init__`` assignment, ``audit_compiled_step_cache``
    catches compile-count drift and closure mutation (and the LiveBackend
    raises through it under the sanitizer);
  * registry/pricing plumbing: ``RingVariant`` expectations equal the
    ``rar_model.wire_formula`` numbers, the fused layout matches
    ``quant_ring.hop_message_layout``;
  * CLI: exit codes, ``--json`` schema shared with the lint, and baseline
    mechanics via the shared ``repro.analysis.baseline`` plumbing.
"""

import json
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import collectives as coll
from repro.analysis import fixtures as fix
from repro.analysis.baseline import Baseline
from repro.dist.registry import RING_VARIANTS, STEP_MODES, variant_by_name
from repro.core.rar_model import wire_formula
from repro.kernels.quant_ring import hop_message_layout
from repro.training.train_step import RING_STEP_MODES

WORLDS = (2, 3, 4)   # acceptance floor: every variant at >= 3 world sizes
DS = (96, 777)       # divisible and padded gradient sizes


# ---------------------------------------------------------------------------
# the repo is clean (the CI gate, as tests)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", RING_VARIANTS,
                         ids=[v.name for v in RING_VARIANTS])
def test_registered_variant_passes_all_axes(variant):
    findings = coll.verify_ring_variant(variant, WORLDS, DS)
    assert findings == [], "\n".join(str(f) for f in findings)


@pytest.mark.parametrize("mode", RING_STEP_MODES)
def test_step_mode_passes_all_axes(mode):
    findings = coll.verify_step_mode(mode, WORLDS)
    assert findings == [], "\n".join(str(f) for f in findings)


@pytest.mark.parametrize("mode", RING_STEP_MODES)
def test_step_mode_has_no_recompile_hazards(mode):
    findings = coll.audit_step_recompilation(mode, 2)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_repo_wide_recompile_audits_clean():
    findings = (coll.audit_optimizer_templates()
                + coll.audit_static_closure()
                + coll.audit_live_group())
    assert findings == [], "\n".join(str(f) for f in findings)


def test_registry_covers_every_step_mode():
    assert set(STEP_MODES) == set(RING_STEP_MODES)
    for mode, spec in STEP_MODES.items():
        if spec.collective == "ppermute":
            assert spec.leaf_variant() in RING_VARIANTS


# ---------------------------------------------------------------------------
# the mutation suite: each axis fails on its deliberately broken jaxpr
# ---------------------------------------------------------------------------

def _fired(variant, w=4, d=777):
    return {f.check for f in coll.verify_ring_variant(variant, [w], [d])}


def test_wrong_permutation_fails_ring_topology():
    broken = [v for v, _ in fix.broken_ring_variants()
              if v.name == "broken-wrong-permutation"][0]
    # w=4: i -> i+2 splits into two 2-cycles
    assert "ring-topology" in _fired(broken)


def test_mixed_direction_fails_ring_topology():
    broken = [v for v, _ in fix.broken_ring_variants()
              if v.name == "broken-mixed-direction"][0]
    fired = _fired(broken)
    assert "ring-topology" in fired
    # each individual perm is a fine cycle — only direction consistency fires
    findings = coll.verify_ring_variant(broken, [4], [777])
    assert any("distinct permutations" in f.message for f in findings)


def test_branch_nested_collective_fails_deadlock_order():
    broken = [v for v, _ in fix.broken_ring_variants()
              if v.name == "broken-branch-nested"][0]
    findings = coll.verify_ring_variant(broken, [4], [777])
    deadlock = [f for f in findings if f.check == "deadlock-order"]
    assert deadlock and "cond" in deadlock[0].message


def test_byte_drift_fails_pricing():
    broken = [v for v, _ in fix.broken_ring_variants()
              if v.name == "broken-f32-payload-int8"][0]
    findings = coll.verify_ring_variant(broken, [4], [777])
    pricing = [f for f in findings if f.check == "pricing"]
    assert pricing, findings
    # message count is deliberately correct; only the bytes drift (4x)
    assert not any("gamma accounting" in f.message for f in pricing)
    assert any("prices" in f.message and "B" in f.message for f in pricing)


def test_trailer_mismatch_fails_pricing():
    broken = [v for v, _ in fix.broken_ring_variants()
              if v.name == "broken-trailer-mismatch"][0]
    findings = coll.verify_ring_variant(broken, [4], [777])
    assert any(f.check == "pricing" and "trailer" in f.message
               for f in findings), findings


def test_fp8_trailer_mismatch_fails_pricing():
    """The short-trailer defect must also fire under fp8 pricing — fp8
    shares the int8 message layout (1 B payload + bitcast f32 trailer)."""
    broken = [v for v, _ in fix.broken_ring_variants()
              if v.name == "broken-fp8-trailer-mismatch"][0]
    findings = coll.verify_ring_variant(broken, [4], [777])
    assert any(f.check == "pricing" and "fp8-fused" in f.message
               for f in findings), findings


def test_bucket_missing_segment_fails_pricing():
    """A bucket pipeline that rings only 2 of its 3 declared segments must
    fail pricing on message count (a silently-unreduced bucket)."""
    broken = [v for v, _ in fix.broken_ring_variants()
              if v.name == "broken-bucket-missing-segment"][0]
    findings = coll.verify_ring_variant(broken, [4], [777])
    pricing = [f for f in findings if f.check == "pricing"]
    assert any("gamma accounting" in f.message for f in pricing), findings


def test_bucket_shared_chain_fails_pricing_on_messages_only():
    """Three declared buckets funneled through ONE ppermute chain carry the
    same total bytes as the per-segment plan — only the per-message gamma
    accounting catches the shared chain."""
    broken = [v for v, _ in fix.broken_ring_variants()
              if v.name == "broken-bucket-shared-chain"][0]
    findings = coll.verify_ring_variant(broken, [4], [777])
    pricing = [f for f in findings if f.check == "pricing"]
    assert any("gamma accounting" in f.message for f in pricing), findings
    # the byte totals coincide by construction: no byte-drift finding
    assert not any("payloads total" in f.message for f in pricing), findings


def test_weak_type_fails_recompile_hazard():
    findings = coll.weak_type_findings(fix.weak_typed_template(), "fixture")
    assert len(findings) == 1
    assert findings[0].check == "recompile-hazard"
    assert "lr_scale" in findings[0].message


def test_self_test_reports_all_axes_firing():
    assert coll.run_self_test() == []


def test_self_test_detects_a_toothless_checker(monkeypatch):
    """If an analysis silently stops firing, the self-test must say so."""
    monkeypatch.setattr(coll, "check_deadlock", lambda sites: [])
    failures = coll.run_self_test()
    assert any("broken-branch-nested" in f for f in failures)


def test_trailer_mismatch_shared_with_kernel_checker():
    """The same seeded trailer defect is rejected at the kernel-config
    layer too — one fixture constant, two analyses."""
    from repro.analysis import kernels as akern

    spec = fix.trailer_mismatch_kernel_spec()
    assert spec.scale_bytes == fix.TRAILER_MISMATCH_SCALE_BYTES
    result = akern.check_spec(spec)
    assert not result.ok
    assert any("scale_bytes" in e for e in result.errors)
    # and the default CLI suite pins it as a must-reject
    assert any(s.scale_bytes == fix.TRAILER_MISMATCH_SCALE_BYTES
               and not expect_ok
               for s, expect_ok in akern.default_suite())


# ---------------------------------------------------------------------------
# topology primitives
# ---------------------------------------------------------------------------

def test_cycle_error_accepts_hamiltonian_cycles():
    for w in (2, 3, 4, 8):
        fwd = tuple((i, (i + 1) % w) for i in range(w))
        rev = tuple((i, (i - 1) % w) for i in range(w))
        assert coll._cycle_error(fwd, w) is None
        assert coll._cycle_error(rev, w) is None


def test_cycle_error_rejects_non_bijections_and_split_cycles():
    # rank 0 sends twice, rank 1 never sends
    assert "bijection" in coll._cycle_error(((0, 1), (0, 2), (2, 0)), 3)
    # two disjoint 2-cycles over 4 ranks
    err = coll._cycle_error(((0, 2), (2, 0), (1, 3), (3, 1)), 4)
    assert "disjoint cycles" in err


def test_bidir_w2_forward_reverse_coincide():
    """At w=2 both directions are the same perm — the bidirectional variant
    must still pass (the sweep includes w=2)."""
    bidir = variant_by_name("bidir")
    findings = coll.verify_ring_variant(bidir, [2], [96])
    assert findings == [], findings


# ---------------------------------------------------------------------------
# pricing agreement with rar_model / quant_ring
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compression", [None, "int8", "int8-fused",
                                         "bf16-fused", "fp8-fused"])
def test_variant_expectations_match_wire_formula(compression):
    name = {None: "f32", "int8": "int8", "int8-fused": "int8-fused",
            "bf16-fused": "bf16-fused", "fp8-fused": "fp8-fused"}
    variant = variant_by_name(name[compression])
    formula = wire_formula(compression)
    for w in WORLDS:
        assert variant.expected_messages(w) == formula.messages(w)
        for d in DS:
            assert variant.expected_bytes(d, w) == pytest.approx(
                formula.bytes_per_worker(d, w, executed=True))


def test_fused_traced_message_is_hop_message_layout():
    """Every fused hop ships one int8 buffer of exactly payload+trailer."""
    variant = variant_by_name("int8-fused")
    w, d = 4, 777
    sites = coll.collect_collectives(coll.trace_ring_variant(variant, w, d))
    layout = hop_message_layout(-(-d // w), block=4096)
    hops = [s for s in sites if s.primitive == "ppermute"]
    assert hops and all(
        s.dtype == "int8" and s.nbytes == layout.message_bytes for s in hops)
    assert layout.message_bytes == layout.payload_bytes + layout.trailer_bytes


def test_collect_collectives_scan_and_guard_tracking():
    def fn(x):
        def body(c, _):
            c = jax.lax.ppermute(c, "ring", [(0, 1), (1, 0)])
            return c, ()
        out, _ = jax.lax.scan(body, x, (), length=3)
        return out

    from jax.sharding import AbstractMesh, PartitionSpec as P
    mesh = AbstractMesh((2,), ("ring",))
    closed = jax.make_jaxpr(jax.shard_map(
        fn, mesh=mesh, in_specs=P("ring"), out_specs=P("ring"),
        check_vma=False))(jax.ShapeDtypeStruct((8,), jnp.float32))
    sites = coll.collect_collectives(closed)
    perms = [s for s in sites if s.primitive == "ppermute"]
    assert sum(s.repeat for s in perms) == 3  # scan length multiplies


# ---------------------------------------------------------------------------
# recompile-hazard audits on mutated inputs
# ---------------------------------------------------------------------------

def test_static_closure_ast_audit_fires_on_mutation(tmp_path):
    src = textwrap.dedent("""
        class RingWorkerGroup:
            STATIC_CLOSURE_ATTRS = ("model", "optimizer", "lr")

            def __init__(self, model):
                self.model = model
                self.lr = 0.1

            def retune(self, lr):
                self.lr = lr        # mutates closed-over static state

            def fine(self):
                self.workers = 2    # not a static attr: allowed
        """)
    path = tmp_path / "elastic_mutated.py"
    path.write_text(src)
    findings = coll.audit_static_closure(str(path))
    assert len(findings) == 1
    f = findings[0]
    assert f.check == "recompile-hazard"
    assert f.symbol == "RingWorkerGroup.retune"
    assert "self.lr" in f.message and f.line > 0


def test_cache_audit_catches_closure_mutation():
    from repro.sched.backend import audit_compiled_step_cache
    from repro.training.elastic import RingWorkerGroup
    from repro.training.optimizer import make_optimizer

    group = RingWorkerGroup(coll._VerifierModel(), make_optimizer("sgdm"),
                            global_batch=8, lr=1e-2, mode="ring")
    assert audit_compiled_step_cache(group) == []
    group.lr = 5e-3  # the hazard: compiled steps closed over the old lr
    problems = audit_compiled_step_cache(group)
    assert problems and "static attrs" in problems[0]


def test_cache_audit_catches_compile_count_drift():
    from repro.sched.backend import audit_compiled_step_cache
    from repro.training.elastic import RingWorkerGroup
    from repro.training.optimizer import make_optimizer

    group = RingWorkerGroup(coll._VerifierModel(), make_optimizer("sgdm"),
                            global_batch=8, lr=1e-2, mode="ring")
    group.compile_count = 3  # claims 3 compiles, zero cached programs
    problems = audit_compiled_step_cache(group)
    assert problems and "compile_count" in problems[0]


def test_compiled_step_cache_hits_on_same_key():
    from repro.training.elastic import RingWorkerGroup
    from repro.training.optimizer import make_optimizer

    group = RingWorkerGroup(coll._VerifierModel(), make_optimizer("sgdm"),
                            global_batch=8, lr=1e-2, mode="ring")
    group._program(1)
    group._program(1)
    assert group.compile_count == 1
    assert group.cache_key(1) == (1, "ring", None, "float32")


def test_step_templates_have_no_weak_types():
    _, params, opt_state, _ = coll.trace_train_step("ring", 2)
    assert coll.weak_type_findings(params, "params") == []
    assert coll.weak_type_findings(opt_state, "opt_state") == []


# ---------------------------------------------------------------------------
# CLI + baseline + --json
# ---------------------------------------------------------------------------

def test_cli_exit_zero_on_repo(tmp_path, capsys):
    out_json = tmp_path / "findings.json"
    rc = coll.main(["--worlds", "2", "3", "4", "--d", "96", "777",
                    "--json", str(out_json)])
    captured = capsys.readouterr().out
    assert rc == 0, captured
    assert "12 variant(s) + 8 step mode(s)" in captured
    data = json.loads(out_json.read_text())
    assert data["tool"] == "repro.analysis.collectives"
    assert data["findings"] == []
    assert data["self_test_failures"] == []
    assert data["stats"]["jaxprs"] >= 12 * 3 * 2  # variants x worlds x ds


def test_cli_json_schema_matches_lint(tmp_path):
    """Both analysis CLIs emit the same per-finding record shape."""
    finding = coll.Finding(check="pricing", path="src/x.py", symbol="s",
                           message="m", line=3)
    record = finding.to_json()
    assert set(record) == {"rule", "path", "line", "symbol", "message",
                           "key"}
    assert record["rule"] == "pricing"
    assert finding.key == "pricing:src/x.py:s"


def test_cli_write_baseline_placeholders_still_fail(tmp_path, monkeypatch):
    """Satellite 1 end-to-end for the verifier: a bootstrapped baseline
    documents findings but cannot silence them."""
    baseline = tmp_path / "collectives_baseline.txt"

    def fake_run_verifier(*a, **k):
        return ([coll.Finding(check="pricing", path="src/x.py", symbol="s",
                              message="drift")], coll.SweepStats())

    monkeypatch.setattr(coll, "run_verifier", fake_run_verifier)
    rc = coll.main(["--write-baseline", "--baseline", str(baseline),
                    "--skip-self-test"])
    assert rc == 0
    assert "TODO justify" in baseline.read_text()

    # the written placeholder is malformed -> still exit 1
    rc = coll.main(["--baseline", str(baseline), "--skip-self-test"])
    assert rc == 1

    # a real justification suppresses it
    baseline.write_text("pricing:src/x.py:s  # accepted drift, see PR 8\n")
    rc = coll.main(["--baseline", str(baseline), "--skip-self-test"])
    assert rc == 0

    # stale entries fail once the finding is gone
    monkeypatch.setattr(coll, "run_verifier",
                        lambda *a, **k: ([], coll.SweepStats()))
    rc = coll.main(["--baseline", str(baseline), "--skip-self-test"])
    assert rc == 1


def test_cli_fails_when_mutation_suite_goes_silent(monkeypatch, capsys):
    monkeypatch.setattr(coll, "run_verifier",
                        lambda *a, **k: ([], coll.SweepStats()))
    monkeypatch.setattr(coll, "run_self_test",
                        lambda *a, **k: ["broken-x: expected pricing"])
    rc = coll.main([])
    assert rc == 1
    assert "MUTATION SUITE NOT FIRING" in capsys.readouterr().out


def test_default_baseline_absent_and_loadable():
    """The shipped sweep is clean, so no baseline file exists — and the
    shared loader treats that as an empty, well-formed baseline."""
    path = coll.default_baseline_path()
    assert not os.path.exists(path)
    loaded = Baseline.load(path)
    assert loaded.entries == {} and loaded.malformed == []
