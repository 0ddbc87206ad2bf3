"""Jaxpr-level collective verifier — ``python -m repro.analysis.collectives``.

GADGET's guarantees assume every scheduled ring behaves exactly as the
analytical model prices it: a ring that deadlocks, sends extra collectives,
or silently recompiles per slot breaks both the Eq. (1) pricing and the
contention model. PR 5 pinned wire bytes for two hand-picked configurations;
this module generalizes that into a static, device-free analysis: every
ring-all-reduce variant registered in :mod:`repro.dist.registry` — and every
``make_ring_train_step`` mode ``RingWorkerGroup`` can run — is traced under
``jax.sharding.AbstractMesh`` across a world-size sweep, and the resulting
jaxprs are verified on four axes:

**(i) ring-topology** — every ``ppermute`` permutation must be a bijection
forming a single Hamiltonian cycle over the axis (a perm that splits into
disjoint cycles reduces only within each cycle: silently wrong sums), and
hop directions must match the variant's declaration — one distinct perm for
unidirectional rings, at most two mutually-inverse perms for the
bidirectional split, none at all for psum variants.

**(ii) deadlock-order** — SPMD collectives only complete when *all* replicas
issue the same sequence. A collective nested under ``lax.cond`` / ``switch``
/ ``while`` whose predicate is data-dependent can diverge across replicas
(one side issues the ppermute, the other does not) and the ring hangs; any
such nesting is flagged.

**(iii) pricing agreement** — the traced collective counts and payload bytes
must equal the scheduler's formulas exactly: ``ppermute`` count vs
``rar_model.compressed_ring_messages`` / ``rar_ring_messages`` (the gamma
multiplier), payload bytes vs ``rar_ring_bytes_per_worker`` /
``rar_compressed_bytes_per_worker`` (evaluated on the executed, padded
layout via :func:`repro.core.rar_model.wire_formula`), and — for the fused
layouts — every hop message must match the declared wire format exactly:
one int8 buffer of ``payload + scale-trailer`` bytes per
:func:`repro.kernels.quant_ring.hop_message_layout` for the int8/fp8 wires,
one bare bfloat16 buffer of the padded chunk for the bf16 wire. Overlap
step modes (``StepModeSpec.n_buckets``) price per *bucket* via the same
``repro.dist.overlap.plan_buckets`` plan the executed reduction uses.

**(iv) recompile-hazard** — ``RingWorkerGroup`` caches compiled steps by
``(workers, mode, n_buckets, wire_dtype)``; anything else influencing the
jit cache key turns the
~6x re-ring advantage into per-slot recompiles. The audit detects weak-typed
leaves in the step's parameter/optimizer-state templates (a Python scalar in
the signature re-keys the cache), dtype drift between a step's input and
output state (every call would retrace), batch-size-dependent collective
structure (shape-dependent Python control flow), non-deterministic tracing
(two traces must produce identical jaxprs), post-``__init__`` assignment of
``RingWorkerGroup.STATIC_CLOSURE_ATTRS`` (checked by AST), and
``compile_count`` drift against the live program cache (cross-checked via
``repro.sched.backend.audit_compiled_step_cache``).

The CLI exits 0 when the repo sweep is clean *and* the seeded mutation suite
(:mod:`repro.analysis.fixtures`) still fires each axis on its deliberately
broken jaxpr — like the kernel checker's must-reject suite, a rejection that
stops firing fails CI. Suppressions use the shared baseline plumbing
(``collectives_baseline.txt`` next to this module, same format and
placeholder rules as the lint; see README.md).
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.analysis.baseline import Baseline, apply_baseline, write_baseline

CHECKS = ("ring-topology", "deadlock-order", "pricing", "recompile-hazard")

AXIS = "ring"                    # the traced mesh axis name
DEFAULT_WORLDS = (2, 3, 4, 8)    # acceptance floor is >= 3 world sizes
DEFAULT_DS = (96, 777)           # one divides every world size, one pads
_STEP_SOURCE = "src/repro/training/train_step.py"
_ELASTIC_SOURCE = "src/repro/training/elastic.py"

# primitives that synchronize across replicas (a superset of what the repo
# emits today, so a new collective cannot slip past the deadlock check)
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "pshuffle", "psum", "pmax", "pmin", "pmean", "all_gather",
    "all_to_all", "reduce_scatter", "pgather",
})
# control-flow primitives whose sub-jaxprs execute conditionally / a
# data-dependent number of times
GUARD_PRIMS = frozenset({"cond", "switch", "while"})


@dataclasses.dataclass(frozen=True)
class Finding:
    """One verifier finding, keyed like a lint violation.

    ``check`` is the axis (doubles as the JSON ``rule``); ``path`` the
    repo-relative source of the offending variant/module; ``symbol`` the
    variant or mode name (stable — no world size, so one baseline entry
    covers the whole sweep); ``message`` carries the (w, d) specifics.
    """

    check: str
    path: str
    symbol: str
    message: str
    line: int = 0

    @property
    def key(self) -> str:
        return f"{self.check}:{self.path}:{self.symbol}"

    def to_json(self) -> Dict:
        return {"rule": self.check, "path": self.path, "line": self.line,
                "symbol": self.symbol, "message": self.message,
                "key": self.key}

    def __str__(self) -> str:
        return (f"{self.path}: [{self.check}] {self.symbol}: {self.message}"
                f"  ({self.key})")


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective equation found in a traced jaxpr."""

    primitive: str
    nbytes: int                    # payload bytes of one issue
    dtype: str
    perm: Optional[Tuple[Tuple[int, int], ...]]
    guards: Tuple[str, ...]        # enclosing cond/switch/while primitives
    repeat: int                    # scan-length multiplier


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(value) -> Iterable:
    """Yield jaxprs nested inside one equation-param value."""
    inner = getattr(value, "jaxpr", value)
    if hasattr(inner, "eqns"):
        yield inner
    elif isinstance(value, (list, tuple)):
        for item in value:
            sub = getattr(item, "jaxpr", item)
            if hasattr(sub, "eqns"):
                yield sub


def collect_collectives(closed_jaxpr) -> List[CollectiveSite]:
    """Every collective in a jaxpr, recursing through pjit/shard_map/
    control-flow sub-jaxprs, with guard context and scan multipliers."""
    sites: List[CollectiveSite] = []

    def walk(jx, guards: Tuple[str, ...], repeat: int) -> None:
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                nbytes = sum(v.aval.size * v.aval.dtype.itemsize
                             for v in eqn.invars
                             if hasattr(v.aval, "size"))
                dtype = str(eqn.invars[0].aval.dtype)
                perm = eqn.params.get("perm")
                if perm is not None:
                    perm = tuple((int(s), int(d)) for s, d in perm)
                sites.append(CollectiveSite(
                    primitive=name, nbytes=int(nbytes), dtype=dtype,
                    perm=perm, guards=guards, repeat=repeat))
            sub_guards = guards + (name,) if name in GUARD_PRIMS else guards
            sub_repeat = repeat
            if name == "scan":
                sub_repeat *= int(eqn.params.get("length", 1))
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub, sub_guards, sub_repeat)

    walk(closed_jaxpr.jaxpr, (), 1)
    return sites


def _ppermute_count(sites: Sequence[CollectiveSite]) -> int:
    return sum(s.repeat for s in sites if s.primitive == "ppermute")


def _ppermute_bytes(sites: Sequence[CollectiveSite]) -> int:
    return sum(s.nbytes * s.repeat for s in sites
               if s.primitive == "ppermute")


# ---------------------------------------------------------------------------
# tracing harness (AbstractMesh: no devices required)
# ---------------------------------------------------------------------------

def trace_ring_variant(variant, w: int, d: int):
    """Trace one registered collective at world size w on a d-element
    gradient; returns the closed jaxpr."""
    mesh = AbstractMesh((w,), (AXIS,))
    fn = jax.shard_map(variant.build(AXIS), mesh=mesh, in_specs=P(AXIS),
                       out_specs=P(AXIS), check_vma=False)
    return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((w * d,), jnp.float32))


class _VerifierModel:
    """Two-leaf linear model with deliberately non-round sizes, so chunk
    padding (the usual pricing-drift hideout) is exercised on every trace."""

    features = 37
    targets = 5

    def init(self, key, dtype=None):
        kw, kb = jax.random.split(key)
        dt = dtype or jnp.float32
        return {
            "w": jax.random.normal(kw, (self.features, self.targets), dt),
            "b": jnp.zeros((self.targets,), dt),
        }

    def loss(self, params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean(jnp.square(pred - batch["y"]))


def _step_templates(model, optimizer, w: int, per_worker_batch: int):
    """Abstract (params, opt_state, global batch) templates for a step."""
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(optimizer.init, params)
    n = w * per_worker_batch
    batch = {
        "x": jax.ShapeDtypeStruct((n, model.features), jnp.float32),
        "y": jax.ShapeDtypeStruct((n, model.targets), jnp.float32),
    }
    return params, opt_state, batch


def trace_train_step(mode: str, w: int, *, per_worker_batch: int = 2,
                     optimizer_name: str = "sgdm"):
    """Trace one full make_ring_train_step mode under AbstractMesh.

    Returns ``(closed_jaxpr, params_template, opt_state_template,
    leaf_sizes)`` — the templates feed the recompile-hazard audit and
    ``leaf_sizes`` the per-leaf pricing expectation.
    """
    from repro.training.optimizer import make_optimizer
    from repro.training.train_step import make_ring_train_step

    model = _VerifierModel()
    optimizer = make_optimizer(optimizer_name)
    step = make_ring_train_step(model, optimizer, AXIS, lr=1e-2, mode=mode)
    mesh = AbstractMesh((w,), (AXIS,))
    smapped = jax.shard_map(step, mesh=mesh,
                            in_specs=(P(), P(), P(AXIS)),
                            out_specs=(P(), P(), P()), check_vma=False)
    params, opt_state, batch = _step_templates(model, optimizer, w,
                                               per_worker_batch)
    closed = jax.make_jaxpr(smapped)(params, opt_state, batch)
    leaf_sizes = [int(leaf.size) for leaf in jax.tree.leaves(params)]
    return closed, params, opt_state, leaf_sizes


# ---------------------------------------------------------------------------
# axis (i): ring topology
# ---------------------------------------------------------------------------

def _cycle_error(perm: Tuple[Tuple[int, int], ...], w: int) -> Optional[str]:
    """Why ``perm`` is not a single Hamiltonian cycle on 0..w-1 (or None)."""
    srcs = sorted(s for s, _ in perm)
    dsts = sorted(d for _, d in perm)
    if srcs != list(range(w)) or dsts != list(range(w)):
        return (f"perm {perm} is not a bijection covering ranks 0..{w - 1} "
                "— some worker never sends or never receives")
    nxt = dict(perm)
    length, cur = 1, nxt[0]
    while cur != 0 and length <= w:
        cur = nxt[cur]
        length += 1
    if length != w:
        return (f"perm {perm} splits the {w}-rank axis into disjoint cycles "
                f"(the cycle through rank 0 has length {length}) — partial "
                "sums never visit every worker, the reduction is silently "
                "wrong")
    return None


def _inverse(perm: Tuple[Tuple[int, int], ...]) -> frozenset:
    return frozenset((d, s) for s, d in perm)


def check_topology(variant, sites: Sequence[CollectiveSite],
                   w: int) -> List[str]:
    """Axis (i) messages for one traced jaxpr."""
    msgs: List[str] = []
    perms: List[Tuple[Tuple[int, int], ...]] = []
    for s in sites:
        if s.primitive == "ppermute" and s.perm is not None:
            perms.append(s.perm)
    if variant.directions == 0:
        if perms:
            msgs.append(f"psum-based variant contains {len(perms)} "
                        "ppermute(s) — no explicit ring is declared")
        return msgs
    distinct: List[Tuple[Tuple[int, int], ...]] = []
    for p in perms:
        if p not in distinct:
            distinct.append(p)
    for p in distinct:
        err = _cycle_error(p, w)
        if err:
            msgs.append(err)
    if msgs:
        return msgs
    if variant.directions == 1 and len(distinct) > 1:
        msgs.append(
            f"hops use {len(distinct)} distinct permutations {distinct} in "
            "a unidirectional ring — chunks must travel one consistent "
            "direction or they bounce instead of walking the cycle")
    elif variant.directions == 2:
        if len(distinct) > 2:
            msgs.append(f"bidirectional ring uses {len(distinct)} distinct "
                        f"permutations {distinct}; expected at most two")
        elif len(distinct) == 2 and \
                frozenset(distinct[0]) != _inverse(distinct[1]):
            msgs.append(
                f"bidirectional ring directions {distinct} are not mutual "
                "inverses — the two half-rings must counter-rotate")
    return msgs


# ---------------------------------------------------------------------------
# axis (ii): deadlock ordering
# ---------------------------------------------------------------------------

def check_deadlock(sites: Sequence[CollectiveSite]) -> List[str]:
    """Axis (ii) messages: collectives under data-dependent control flow."""
    msgs: List[str] = []
    seen = set()
    for s in sites:
        if not s.guards:
            continue
        sig = (s.primitive, s.guards)
        if sig in seen:
            continue
        seen.add(sig)
        chain = " -> ".join(s.guards)
        msgs.append(
            f"{s.primitive} nested under lax.{chain} — replicas whose "
            "predicate disagrees issue mismatched collective sequences and "
            "the ring deadlocks; hoist the collective out of the branch or "
            "make the predicate replica-invariant")
    return msgs


# ---------------------------------------------------------------------------
# axis (iii): pricing agreement
# ---------------------------------------------------------------------------

def _fused_message_errors(sites: Sequence[CollectiveSite], d: int, w: int,
                          compression: str = "int8-fused") -> List[str]:
    """Per-message layout check for the fused wire formats.

    int8 and fp8 payloads travel bitcast to one int8 buffer of exactly
    ``payload + scale-trailer`` bytes; the bf16 wire is a bare bfloat16
    buffer of the padded chunk (2 B/element, no trailer).
    """
    from repro.dist.compression import DEFAULT_BLOCK
    from repro.kernels.quant_ring import hop_message_layout

    layout = hop_message_layout(-(-d // w), block=DEFAULT_BLOCK)
    if compression == "bf16-fused":
        want_dtype = "bfloat16"
        want_bytes = 2 * layout.payload_bytes  # padded chunk, no trailer
        expect = (f"bfloat16[{want_bytes} B] (2 B x {layout.payload_bytes} "
                  "padded elements, no scale trailer)")
    else:  # int8-fused / fp8-fused: 1 B payload + bitcast f32 scale trailer
        want_dtype = "int8"
        want_bytes = layout.message_bytes
        expect = (f"int8[{want_bytes} B] ({layout.payload_bytes} payload + "
                  f"{layout.trailer_bytes} trailer)")
    msgs: List[str] = []
    seen = set()
    for s in sites:
        if s.primitive != "ppermute":
            continue
        sig = (s.dtype, s.nbytes)
        if sig in seen:
            continue
        seen.add(sig)
        if s.dtype != want_dtype or s.nbytes != want_bytes:
            msgs.append(
                f"fused hop message is {s.dtype}[{s.nbytes} B] but the "
                f"{compression} layout for a {-(-d // w)}-element chunk is "
                f"{expect} — kernel wire format and scheduler pricing have "
                "drifted")
    return msgs


def check_pricing(variant, sites: Sequence[CollectiveSite], w: int,
                  d: int) -> List[str]:
    """Axis (iii) messages for one traced jaxpr vs the rar_model formulas."""
    msgs: List[str] = []
    count = _ppermute_count(sites)
    expected = variant.expected_messages(w, d)
    if count != expected:
        msgs.append(
            f"traced jaxpr issues {count} ppermute(s) but rar_model prices "
            f"{expected} message(s) for w={w} "
            f"(compression={variant.compression!r}) — the per-message gamma "
            "accounting is wrong")
    if variant.collective == "ppermute":
        total = _ppermute_bytes(sites)
        expect_bytes = variant.expected_bytes(d, w)
        if abs(total - expect_bytes) > 1e-6 * max(expect_bytes, 1.0):
            msgs.append(
                f"traced ppermute payloads total {total} B but rar_model "
                f"prices {expect_bytes:g} B for d={d}, w={w} "
                f"(compression={variant.compression!r}) — Eq. (1)'s wire "
                "term no longer matches what the ring sends")
        if variant.compression in ("int8-fused", "bf16-fused", "fp8-fused") \
                and not variant.n_buckets:
            msgs.extend(_fused_message_errors(sites, d, w,
                                              variant.compression))
        extras = sorted({s.primitive for s in sites
                         if s.primitive != "ppermute"})
        if extras:
            msgs.append(
                f"ring variant also issues unpriced collective(s) "
                f"{extras} — rar_model prices ppermutes only")
    else:  # psum-based variant
        n_psum = sum(s.repeat for s in sites if s.primitive == "psum")
        if n_psum != 1:
            msgs.append(f"psum variant issues {n_psum} psum(s); expected "
                        "exactly 1 all-reduce")
    return msgs


def check_step_pricing(spec, sites: Sequence[CollectiveSite], w: int,
                       leaf_sizes: Sequence[int]) -> List[str]:
    """Axis (iii) for a full train step: per-leaf reduction + loss pmean."""
    msgs: List[str] = []
    n_leaves = len(leaf_sizes)
    psums = [s for s in sites if s.primitive == "psum"]
    n_psum = sum(s.repeat for s in psums)
    count = _ppermute_count(sites)
    if spec.collective == "psum":
        if count:
            msgs.append(f"psum mode traces {count} ppermute(s); expected 0")
        if n_psum != n_leaves + 1:
            msgs.append(
                f"psum mode traces {n_psum} psum(s); expected "
                f"{n_leaves + 1} ({n_leaves} grad leaves + 1 loss pmean)")
        return msgs
    leaf_variant = spec.leaf_variant()
    if spec.n_buckets:
        # overlap mode: one ring per planned bucket, not per leaf — price
        # with the identical reverse-autodiff plan the executed reduction
        # uses (overlap.plan_buckets), so they cannot drift apart
        from repro.dist.overlap import plan_bucket_sizes

        payloads = list(plan_bucket_sizes(leaf_sizes, spec.n_buckets,
                                          reverse=True))
        unit = f"{len(payloads)} bucket(s) over leaves {list(leaf_sizes)}"
    else:
        payloads = list(leaf_sizes)
        unit = f"{n_leaves} leaves"
    expected = sum(leaf_variant.expected_messages(w) for _ in payloads)
    if count != expected:
        msgs.append(
            f"step traces {count} ppermute(s) but rar_model prices "
            f"{expected} ({unit} x "
            f"{leaf_variant.expected_messages(w)}) for w={w}")
    total = _ppermute_bytes(sites)
    expect_bytes = sum(leaf_variant.expected_bytes(size, w)
                       for size in payloads)
    if abs(total - expect_bytes) > 1e-6 * max(expect_bytes, 1.0):
        msgs.append(
            f"step ppermute payloads total {total} B but rar_model prices "
            f"{expect_bytes:g} B over {unit} at w={w}")
    if n_psum != 1:
        msgs.append(f"step traces {n_psum} psum(s); expected exactly 1 "
                    "(the loss pmean) — extra collectives are unpriced")
    elif psums and psums[0].nbytes != 4:
        msgs.append(f"the loss pmean carries {psums[0].nbytes} B; expected "
                    "a 4 B f32 scalar")
    return msgs


# ---------------------------------------------------------------------------
# axis (iv): recompilation hazards
# ---------------------------------------------------------------------------

def weak_type_findings(tree, origin: str,
                       path: str = _STEP_SOURCE) -> List[Finding]:
    """Weak-typed leaves in an abstract template (jit cache-key hazard)."""
    out: List[Finding] = []
    for key_path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if getattr(leaf, "weak_type", False):
            where = jax.tree_util.keystr(key_path)
            out.append(Finding(
                check="recompile-hazard", path=path, symbol=origin,
                message=(
                    f"leaf {where} of the {origin} template is weak-typed "
                    f"({leaf.dtype}) — a Python scalar in the compiled "
                    "step's signature re-keys the jit cache against every "
                    "strongly-typed caller, defeating the (workers, mode) "
                    "cache")))
    return out


def _collective_profile(sites: Sequence[CollectiveSite]) -> Tuple:
    """Order-preserving summary used to compare two traces structurally."""
    return tuple((s.primitive, s.dtype, s.nbytes, s.perm, s.guards, s.repeat)
                 for s in sites)


def _leaves_with_paths(tree):
    return [(jax.tree_util.keystr(kp), leaf) for kp, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]]


def audit_step_recompilation(mode: str, w: int) -> List[Finding]:
    """Axis (iv) for one (mode, w): weak types, dtype drift, batch-shape
    sensitivity, and trace determinism of the compiled step."""
    findings: List[Finding] = []
    symbol = f"make_ring_train_step[{mode}]"
    closed, params, opt_state, _ = trace_train_step(mode, w)
    findings.extend(weak_type_findings(params, f"{symbol} params"))
    findings.extend(weak_type_findings(opt_state, f"{symbol} opt_state"))

    # dtype promotion: the step's output state templates must match its
    # inputs exactly (shape, dtype, weak type), else every slot's step call
    # feeds back a drifted pytree and retraces
    n_params = len(jax.tree.leaves(params))
    n_opt = len(jax.tree.leaves(opt_state))
    out_flat = list(closed.out_avals)
    out_params = out_flat[:n_params]
    out_opt = out_flat[n_params:n_params + n_opt]
    for (where, tmpl), out in zip(
            _leaves_with_paths(params) + _leaves_with_paths(opt_state),
            out_params + out_opt):
        drift = []
        if tuple(out.shape) != tuple(tmpl.shape):
            drift.append(f"shape {tuple(tmpl.shape)} -> {tuple(out.shape)}")
        if out.dtype != tmpl.dtype:
            drift.append(f"dtype {tmpl.dtype} -> {out.dtype}")
        if bool(getattr(out, "weak_type", False)) != \
                bool(getattr(tmpl, "weak_type", False)):
            drift.append("weak_type flipped")
        if drift:
            findings.append(Finding(
                check="recompile-hazard", path=_STEP_SOURCE, symbol=symbol,
                message=(
                    f"state leaf {where} drifts across one step "
                    f"({', '.join(drift)}) at w={w} — feeding the output "
                    "back in retraces the jitted step every slot")))

    # determinism: tracing twice must give the identical jaxpr
    closed2, _, _, _ = trace_train_step(mode, w)
    if str(closed) != str(closed2):
        findings.append(Finding(
            check="recompile-hazard", path=_STEP_SOURCE, symbol=symbol,
            message=f"two traces of the same (mode={mode}, w={w}) step "
                    "produce different jaxprs — tracing is nondeterministic "
                    "(unstable iteration order or fresh closures per trace)"))

    # batch-size independence: the collective structure must not depend on
    # the per-worker batch (gradients have fixed shapes); a difference means
    # shape-dependent Python control flow reached the ring
    big, _, _, _ = trace_train_step(mode, w, per_worker_batch=4)
    p_small = _collective_profile(collect_collectives(closed))
    p_big = _collective_profile(collect_collectives(big))
    if p_small != p_big:
        findings.append(Finding(
            check="recompile-hazard", path=_STEP_SOURCE, symbol=symbol,
            message=(
                f"collective structure changes with the per-worker batch "
                f"size at w={w} ({len(p_small)} vs {len(p_big)} sites) — "
                "shape-dependent control flow reaches the ring, so every "
                "batch geometry recompiles a different collective program")))
    return findings


def audit_optimizer_templates() -> List[Finding]:
    """Weak-typed leaves in every registered optimizer's state template."""
    from repro.training.optimizer import make_optimizer

    findings: List[Finding] = []
    model = _VerifierModel()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    for name in ("adamw", "adafactor", "sgdm"):
        opt = make_optimizer(name)
        state = jax.eval_shape(opt.init, params)
        findings.extend(weak_type_findings(
            state, f"optimizer[{name}] state",
            path="src/repro/training/optimizer.py"))
    return findings


def _class_static_attrs(cls_node: ast.ClassDef) -> Tuple[str, ...]:
    """Read STATIC_CLOSURE_ATTRS from a class body (string-literal tuple)."""
    for node in cls_node.body:
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and \
                        tgt.id == "STATIC_CLOSURE_ATTRS":
                    try:
                        value = ast.literal_eval(node.value)
                    except ValueError:
                        return ()
                    return tuple(str(v) for v in value)
    return ()


def audit_static_closure(source_path: Optional[str] = None) -> List[Finding]:
    """AST check: no method outside ``__init__`` assigns a static closure
    attr of a class declaring ``STATIC_CLOSURE_ATTRS`` (RingWorkerGroup)."""
    if source_path is None:
        import repro.training.elastic as elastic_mod

        source_path = elastic_mod.__file__
    with open(source_path) as f:
        tree = ast.parse(f.read(), source_path)
    findings: List[Finding] = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        attrs = _class_static_attrs(cls)
        if not attrs:
            continue
        for method in cls.body:
            if not isinstance(method, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                continue
            if method.name == "__init__":
                continue
            for node in ast.walk(method):
                if not isinstance(node, (ast.Assign, ast.AugAssign,
                                         ast.AnnAssign)):
                    continue
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for tgt in targets:
                    if isinstance(tgt, ast.Attribute) and \
                            isinstance(tgt.value, ast.Name) and \
                            tgt.value.id == "self" and tgt.attr in attrs:
                        findings.append(Finding(
                            check="recompile-hazard",
                            path=_ELASTIC_SOURCE,
                            symbol=f"{cls.name}.{method.name}",
                            line=node.lineno,
                            message=(
                                f"self.{tgt.attr} (a STATIC_CLOSURE_ATTRS "
                                "entry the compiled steps close over) is "
                                f"assigned in {method.name}() — mutating it "
                                "after __init__ serves stale executables "
                                "under the (workers, mode) cache key")))
    return findings


def audit_live_group() -> List[Finding]:
    """compile_count / cache-key cross-check on a live RingWorkerGroup.

    Cheap on any backend: ``_program`` builds (but never executes) the
    jitted step, so this works on the single-CPU test container too.
    """
    from repro.sched.backend import audit_compiled_step_cache
    from repro.training.elastic import RingWorkerGroup, largest_feasible_ring
    from repro.training.optimizer import make_optimizer

    findings: List[Finding] = []
    group = RingWorkerGroup(_VerifierModel(), make_optimizer("sgdm"),
                            global_batch=8, lr=1e-2, mode="ring")
    group._program(1)
    group._program(1)  # same key: must be a cache hit
    if group.compile_count != 1:
        findings.append(Finding(
            check="recompile-hazard", path=_ELASTIC_SOURCE,
            symbol="RingWorkerGroup._program",
            message=(
                f"two _program() calls at one (workers, mode) key compiled "
                f"{group.compile_count} time(s); expected 1 — equal-sized "
                "back-to-back slots are re-tracing")))
    for problem in audit_compiled_step_cache(group):
        findings.append(Finding(
            check="recompile-hazard", path=_ELASTIC_SOURCE,
            symbol="RingWorkerGroup", message=problem))
    # worker-count resolution must be idempotent: requested sizes that clamp
    # to the same feasible ring share one cache entry
    for gb in (8, 12):
        for req in range(1, 10):
            resolved = largest_feasible_ring(req, global_batch=gb,
                                             n_devices=8)
            again = largest_feasible_ring(resolved, global_batch=gb,
                                          n_devices=8)
            if resolved != again:
                findings.append(Finding(
                    check="recompile-hazard", path=_ELASTIC_SOURCE,
                    symbol="largest_feasible_ring",
                    message=(
                        f"resolution is not idempotent: requested={req} -> "
                        f"{resolved} -> {again} (global_batch={gb}) — "
                        "aliased requests would split the compiled-step "
                        "cache")))
    return findings


# ---------------------------------------------------------------------------
# sweeps
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SweepStats:
    variants: int = 0
    step_modes: int = 0
    jaxprs: int = 0
    collectives: int = 0
    worlds: Tuple[int, ...] = ()


def verify_ring_variant(variant, worlds: Sequence[int],
                        ds: Sequence[int],
                        stats: Optional[SweepStats] = None) -> List[Finding]:
    """All four static axes for one registered collective across the sweep."""
    findings: List[Finding] = []
    for w in worlds:
        for d in ds:
            closed = trace_ring_variant(variant, w, d)
            sites = collect_collectives(closed)
            if stats is not None:
                stats.jaxprs += 1
                stats.collectives += len(sites)
            for msg in check_topology(variant, sites, w):
                findings.append(Finding(
                    check="ring-topology", path=variant.source,
                    symbol=variant.name, message=f"[w={w}, d={d}] {msg}"))
            for msg in check_deadlock(sites):
                findings.append(Finding(
                    check="deadlock-order", path=variant.source,
                    symbol=variant.name, message=f"[w={w}, d={d}] {msg}"))
            for msg in check_pricing(variant, sites, w, d):
                findings.append(Finding(
                    check="pricing", path=variant.source,
                    symbol=variant.name, message=f"[w={w}, d={d}] {msg}"))
    return findings


def verify_step_mode(mode: str, worlds: Sequence[int],
                     stats: Optional[SweepStats] = None) -> List[Finding]:
    """Axes (i)-(iii) for one full train-step mode across the sweep."""
    from repro.dist.registry import STEP_MODES

    spec = STEP_MODES[mode]
    symbol = f"make_ring_train_step[{mode}]"
    findings: List[Finding] = []
    for w in worlds:
        closed, _, _, leaf_sizes = trace_train_step(mode, w)
        sites = collect_collectives(closed)
        if stats is not None:
            stats.jaxprs += 1
            stats.collectives += len(sites)
        for msg in check_topology(spec, sites, w):
            findings.append(Finding(
                check="ring-topology", path=_STEP_SOURCE, symbol=symbol,
                message=f"[w={w}] {msg}"))
        for msg in check_deadlock(sites):
            findings.append(Finding(
                check="deadlock-order", path=_STEP_SOURCE, symbol=symbol,
                message=f"[w={w}] {msg}"))
        for msg in check_step_pricing(spec, sites, w, leaf_sizes):
            findings.append(Finding(
                check="pricing", path=_STEP_SOURCE, symbol=symbol,
                message=f"[w={w}] {msg}"))
    return findings


def run_verifier(worlds: Sequence[int] = DEFAULT_WORLDS,
                 ds: Sequence[int] = DEFAULT_DS, *,
                 include_steps: bool = True,
                 include_recompile: bool = True,
                 ) -> Tuple[List[Finding], SweepStats]:
    """The full repo sweep: every registered variant and step mode."""
    from repro.dist.registry import RING_VARIANTS
    from repro.training.train_step import RING_STEP_MODES

    stats = SweepStats(worlds=tuple(worlds))
    findings: List[Finding] = []
    for variant in RING_VARIANTS:
        stats.variants += 1
        findings.extend(verify_ring_variant(variant, worlds, ds, stats))
    if include_steps:
        step_worlds = [w for w in worlds if w != max(worlds)] or list(worlds)
        for mode in RING_STEP_MODES:
            stats.step_modes += 1
            findings.extend(verify_step_mode(mode, step_worlds, stats))
            if include_recompile:
                findings.extend(audit_step_recompilation(
                    mode, min(step_worlds)))
    if include_recompile:
        findings.extend(audit_optimizer_templates())
        findings.extend(audit_static_closure())
        findings.extend(audit_live_group())
    return findings, stats


# ---------------------------------------------------------------------------
# the seeded mutation suite (must fire — like kernels' must-reject configs)
# ---------------------------------------------------------------------------

def run_self_test(w: int = 4, d: int = 777) -> List[str]:
    """Trace each deliberately broken fixture and return the axes that
    FAILED to fire (empty = every analysis still has teeth)."""
    from repro.analysis.fixtures import (
        broken_ring_variants,
        weak_typed_template,
    )

    failures: List[str] = []
    for variant, expect_check in broken_ring_variants():
        findings = verify_ring_variant(variant, [w], [d])
        fired = {f.check for f in findings}
        if expect_check not in fired:
            failures.append(
                f"{variant.name}: expected a {expect_check} finding, got "
                f"{sorted(fired) or 'none'}")
    weak = weak_type_findings(weak_typed_template(), "weak-typed fixture")
    if not any(f.check == "recompile-hazard" for f in weak):
        failures.append("weak_typed_template: expected a recompile-hazard "
                        "finding on the weak-typed scalar leaf")
    return failures


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "collectives_baseline.txt")


def findings_json(findings: Sequence[Finding], baseline: Baseline,
                  stats: SweepStats, self_test_failures: List[str]) -> Dict:
    new, stale = apply_baseline(findings, baseline)
    new_keys = {f.key for f in new}
    return {
        "tool": "repro.analysis.collectives",
        "findings": [dict(f.to_json(), baselined=f.key not in new_keys)
                     for f in findings],
        "stale": stale,
        "malformed": list(baseline.malformed),
        "self_test_failures": self_test_failures,
        "stats": dataclasses.asdict(stats),
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis.collectives",
        description="static jaxpr verifier for every registered ring "
                    "collective (module docstring has the four axes)")
    parser.add_argument("--worlds", type=int, nargs="+",
                        default=list(DEFAULT_WORLDS),
                        help="world sizes to sweep (default: %(default)s)")
    parser.add_argument("--d", type=int, nargs="+", dest="ds",
                        default=list(DEFAULT_DS),
                        help="gradient sizes to sweep (default: %(default)s"
                             " — one divisible by every world, one padded)")
    parser.add_argument("--skip-steps", action="store_true",
                        help="skip the full train-step mode sweep")
    parser.add_argument("--skip-recompile", action="store_true",
                        help="skip the recompilation-hazard audit")
    parser.add_argument("--skip-self-test", action="store_true",
                        help="skip the seeded mutation suite (it must fire "
                             "one finding per broken fixture)")
    parser.add_argument("--baseline", default=None,
                        help="baseline file (default: "
                             "repro/analysis/collectives_baseline.txt)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="report every finding, ignoring the baseline")
    parser.add_argument("--write-baseline", action="store_true",
                        help="write current findings as the baseline; "
                             "placeholder entries still fail the gate "
                             "until justified")
    parser.add_argument("--json", default=None, metavar="PATH",
                        help="also write machine-readable findings "
                             "(rule/path/line/symbol/message) to PATH")
    args = parser.parse_args(argv)

    findings, stats = run_verifier(
        args.worlds, args.ds, include_steps=not args.skip_steps,
        include_recompile=not args.skip_recompile)
    baseline_path = args.baseline or default_baseline_path()

    if args.write_baseline:
        n = write_baseline(baseline_path, (f.key for f in findings),
                           tool="repro.analysis.collectives")
        print(f"wrote {n} baseline entries -> {baseline_path}")
        print("placeholder justifications still FAIL the gate — replace "
              "each 'TODO justify' with a real rationale")
        return 0

    baseline = Baseline(entries={}, malformed=[]) if args.no_baseline \
        else Baseline.load(baseline_path)
    self_test_failures: List[str] = []
    if not args.skip_self_test:
        self_test_failures = run_self_test()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(findings_json(findings, baseline, stats,
                                    self_test_failures), f, indent=2)

    new, stale = apply_baseline(findings, baseline)
    status = 0
    for f in new:
        print(f"collectives: {f}")
        status = 1
    for line in baseline.malformed:
        print("collectives: baseline entry missing or placeholder "
              f"justification: {line}")
        status = 1
    for key in stale:
        print("collectives: stale baseline entry (finding no longer fires "
              f"— delete the line): {key}")
        status = 1
    for failure in self_test_failures:
        print(f"collectives: MUTATION SUITE NOT FIRING: {failure}")
        status = 1
    suppressed = len(findings) - len(new)
    self_test = "skipped" if args.skip_self_test else \
        f"{len(self_test_failures)} silent"
    print(f"collectives: {stats.variants} variant(s) + {stats.step_modes} "
          f"step mode(s) at worlds {list(stats.worlds)}: {stats.jaxprs} "
          f"jaxpr(s), {stats.collectives} collective(s); "
          f"{len(findings)} finding(s), {suppressed} baselined, "
          f"{len(new)} new, {len(stale)} stale; mutation suite: "
          f"{self_test} -> {'FAIL' if status else 'OK'}")
    return status


if __name__ == "__main__":
    sys.exit(main())
