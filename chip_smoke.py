"""Chip smoke test: GADGET's main path at qwen3-0.6b's published widths.

One process, the repo's own entry points, random weights from fixed seeds.

  python3 chip_smoke.py            # one chip (the default)
  python3 chip_smoke.py --chips 4  # one v5e 2x2 host, ring phase only

One chip runs two phases:

  * train — GADGET (registry name ``gadget``) in ``OnlineDriver`` with a
    ``LiveBackend`` binds three slots of a one-server, one-chip cluster to
    a full-width ``ElasticTrainer`` (bf16 params, adamw, ``ring`` mode,
    global batch 8 x 1024 tokens, the same batch every step);
  * serve — ``ServingEngine`` on the full-width model with bf16 params
    serves seeded requests through ``serve_requests``; the engine's own
    prefill logits for one request (other lanes live) are checked against
    a float32 ``model.forward``, and every served token against that
    forward's argmax.

``--chips 4`` runs full-width ``ElasticTrainer`` rings at w=4 in ``ring``
and ``compressed-fused`` against ``psum`` from the same seed and batches,
with a planted fault (no reduction) that the tolerances must reject,
checks the Pallas wire kernels were compiled for the TPU, and re-rings one
trainer from 4 to 2 chips mid-slot.

Without a TPU the script exits 2 before any phase. Any failed check exits
1. Only when every phase passed is the last line of stdout
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "src"))

ARCH = "qwen3-0.6b"

# training: first loss of a random-init LM sits near ln(vocab) (logits of
# unit spread add ~0.5 nat); after the steps on one repeated batch the loss
# must have fallen by at least LOSS_DROP
FIRST_LOSS_BAND = (-0.5, 1.5)    # allowed first loss - ln(vocab)
LOSS_DROP = 0.1

# serving: bf16 engine logits against a float32 reference forward. Logits
# of the random-init model have unit spread; a wrong position or a stale
# cache moves them by O(1), bf16 rounding over 28 layers by a few 0.01
LOGIT_MAX_ABS_ERR = 0.5
LOGIT_REL_RMS_ERR = 0.05

# four chips, after RING_STEPS steps from the same seed and batches:
# |loss - psum loss| and ||dp - dp_psum|| / ||dp_psum|| (dp = params moved
# by training) for the bf16-gradient f32-ring and the int8 wire
RING_STEPS = 3
RING_TOL = {"ring": (0.02, 0.1), "compressed-fused": (0.05, 0.5)}
# a planted fault the tolerances must reject: the ring's reduction replaced
# by the identity, so each chip trains on its own quarter of the batch
FAULT = "local-only"


class RepeatedBatch:
    """The first batch of a ``SyntheticTokens`` stream at every step."""

    def __init__(self, inner):
        self._batch = inner.batch(0)

    def batch(self, step: int):
        return self._batch


@dataclasses.dataclass
class CompileTimer:
    """Seconds JAX spent tracing, lowering and compiling (cache hits skip
    the backend compile)."""

    seconds: float = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# one chip: GADGET -> OnlineDriver -> LiveBackend -> ElasticTrainer
# ---------------------------------------------------------------------------

def train_phase(cfg, *, global_batch: int, seq: int, slots: int,
                steps_per_slot: int, lr: float, param_dtype) -> dict:
    import jax

    from repro.cluster import make_fat_tree
    from repro.core.problem import DDLJSInstance, Job
    from repro.core.rar_model import profile_from_arch
    from repro.core.utility import sqrt_utility
    from repro.data.pipeline import SyntheticTokens
    from repro.models.model import build_model
    from repro.sched import LiveBackend, OnlineDriver, registry
    from repro.sched.backend import audit_compiled_step_cache
    from repro.training.elastic import ElasticTrainer, SlotPlan
    from repro.training.optimizer import make_optimizer

    model = build_model(cfg)
    n_params = cfg.n_params()
    prof = profile_from_arch(n_params=float(n_params),
                             tokens_per_batch=float(global_batch * seq))
    job = Job(id=0, arrival=0, max_workers=1,
              demands={"gpus": 1.0, "mem": 1.0}, budgets={"gpus": 100.0},
              bandwidth=1e9, zeta=1.0, utility=sqrt_utility(10.0),
              profile=prof, arch=cfg.name)
    graph = make_fat_tree(n_servers=1, n_racks=1, n_core=1,
                          gpus_choices=(1,), seed=0)
    inst = DDLJSInstance(graph=graph, jobs=[job], horizon=slots)
    trainer = ElasticTrainer(
        model, make_optimizer(cfg.optimizer),
        RepeatedBatch(SyntheticTokens(cfg.vocab, seq, global_batch, seed=0)),
        global_batch=global_batch, base_lr=lr, mode="ring",
        param_dtype=param_dtype)
    backend = LiveBackend({job.id: trainer}, steps_per_slot=steps_per_slot,
                          calibrate=False)
    driver = OnlineDriver(inst, backend=backend)

    timer = CompileTimer()
    jax.monitoring.register_event_duration_secs_listener(timer)
    try:
        t0 = time.perf_counter()
        result = driver.run(registry.create("gadget", seed=0))
        jax.block_until_ready(trainer.params)
        run_seconds = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(timer)
    # one more slot straight through the trainer, for its own warm step time
    warm = trainer.run_slot(SlotPlan(workers=1, steps=2))
    losses = list(trainer.losses)
    out = {
        "n_params": n_params,
        "params_dtype": str(jax.tree.leaves(trainer.params)[0].dtype),
        "global_batch": global_batch, "seq": seq,
        "slots_run": len(backend.reports), "losses": losses,
        "first_minus_ln_vocab": losses[0] - math.log(cfg.vocab),
        "warm_step_seconds": warm["timings"].get(1),
        "driver_run_seconds": run_seconds,
        "compile_seconds": timer.seconds,
        "compile_count": trainer.group.compile_count,
        "utility": result.total_utility,
        "peak_bytes": peak_bytes(jax.devices()[0]),
    }
    print(json.dumps({"train": out}), flush=True)
    check(len(backend.reports) == slots,
          f"GADGET ran the job in all {slots} slots")
    check(all(math.isfinite(x) for x in losses), "losses are finite")
    lo, hi = FIRST_LOSS_BAND
    check(lo <= out["first_minus_ln_vocab"] <= hi,
          f"first loss {losses[0]:.4f} within [{lo}, {hi}] of "
          f"ln(vocab)={math.log(cfg.vocab):.4f}")
    check(losses[-1] < losses[0] - LOSS_DROP,
          f"loss fell by more than {LOSS_DROP} on the repeated batch")
    check(trainer.group.compile_count == 1, "one compile for the ring size")
    check(not audit_compiled_step_cache(trainer.group),
          "compiled-step cache audit is clean")
    return out


# ---------------------------------------------------------------------------
# one chip: ServingEngine, checked against a float32 forward
# ---------------------------------------------------------------------------

def serve_phase(cfg, *, n_requests: int, prompt_len: int, max_new: int,
                max_batch: int, prefill_chunk: int, param_dtype) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import (
        Request,
        ServingEngine,
        audit_serving_engine,
        serve_requests,
    )
    from repro.models.model import build_model

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1), dtype=param_dtype)
    rng = np.random.default_rng(2)
    reqs = [Request(id=i, prompt=rng.integers(0, cfg.vocab, prompt_len,
                                              dtype=np.int32),
                    max_new=max_new)
            for i in range(n_requests)]
    max_seq = prompt_len + max_new
    engine = ServingEngine(model, params, max_batch=max_batch,
                           max_seq=max_seq, prefill_chunk=prefill_chunk)
    # the engine's own prefill output at each prompt's last chunk is the
    # logits at that request's first decoded position; record it with the
    # number of lanes already live in the shared cache while it ran
    first_logits = []
    prefill = engine._prefill

    def recording_prefill(params, cache, lane, tokens, pos0, n_total):
        cache, last = prefill(params, cache, lane, tokens, pos0, n_total)
        if int(pos0) + tokens.shape[1] >= int(n_total):
            first_logits.append((int(engine.active.sum()), last))
        return cache, last

    engine._prefill = recording_prefill
    t0 = time.perf_counter()
    done = serve_requests(engine, reqs)
    serve_seconds = time.perf_counter() - t0
    problems = audit_serving_engine(engine)
    check(len(done) == n_requests and all(
        len(r.tokens) == max_new for r in done),
        f"all {n_requests} requests served with {max_new} tokens each")
    check(engine.compile_count == 1, "decode_compiles == 1")
    check(not problems, f"audit_serving_engine clean {problems}")

    # float32 reference: the same weights, one full forward over each
    # prompt and all but the last served token; position prompt_len-1+j
    # is the one that chose served token j
    by_id = sorted(done, key=lambda r: r.id)
    served = np.array([r.tokens for r in by_id], np.int32)
    toks = np.concatenate([np.stack([r.prompt for r in by_id]),
                           served[:, :-1]], axis=1)
    ref_params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref_logits, _ = jax.jit(model.forward)(ref_params,
                                               {"tokens": jnp.asarray(toks)})
    ref = np.asarray(ref_logits[:, prompt_len - 1:, :cfg.vocab], np.float32)
    del ref_params, ref_logits
    in_vocab = bool(served.max() < cfg.vocab)
    gaps = ref.max(-1) - np.take_along_axis(
        ref, np.minimum(served, cfg.vocab - 1)[..., None], -1)[..., 0]

    # the engine's logits for the request admitted with the most lanes live
    k = max(range(len(first_logits)), key=lambda i: first_logits[i][0])
    live, last = first_logits[k]
    got = np.asarray(last[:cfg.vocab], np.float32)
    d = got - ref[k, 0]
    max_abs = float(np.abs(d).max())
    rel_rms = float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(ref[k, 0] ** 2)))
    out = {
        "requests": n_requests, "served": len(done),
        "tokens": sum(len(r.tokens) for r in done),
        "decode_compiles": engine.compile_count,
        "prefill_compiles": engine.prefill_compile_count,
        "decode_steps": engine.decode_steps,
        "serve_seconds": serve_seconds,
        "logit_check_request": by_id[k].id, "lanes_live_at_prefill": live,
        "first_logits_max_abs_err": max_abs, "first_logits_rel_rms": rel_rms,
        "served_tokens_checked": int(served.size),
        "served_argmax_agreement": float(np.mean(ref.argmax(-1) == served)),
        "served_max_ref_gap": float(gaps.max()),
        "peak_bytes": peak_bytes(jax.devices()[0]),
    }
    print(json.dumps({"serve": out}), flush=True)
    check(len(first_logits) == n_requests and live >= min(
        n_requests, max_batch) - 1,
        f"the logit check reads the engine's own prefill with {live} other "
        "lanes live")
    check(max_abs <= LOGIT_MAX_ABS_ERR and rel_rms <= LOGIT_REL_RMS_ERR,
          f"engine logits at request {by_id[k].id}'s first decoded position "
          f"match the f32 forward (max abs {max_abs:.4f} <= "
          f"{LOGIT_MAX_ABS_ERR}, rel rms {rel_rms:.4f} <= "
          f"{LOGIT_REL_RMS_ERR})")
    check(in_vocab and out["served_max_ref_gap"] <= LOGIT_MAX_ABS_ERR,
          f"every one of the {served.size} served tokens is the f32 "
          f"forward's argmax within the logit tolerance (largest gap "
          f"{out['served_max_ref_gap']:.4f})")
    return out


# ---------------------------------------------------------------------------
# four chips: ring and compressed-fused against psum, then re-ring 4 -> 2
# ---------------------------------------------------------------------------

def _local_only(g, axis_name):
    """Planted fault: each chip keeps its own gradient (no reduction)."""
    return g


def ring_phase(cfg, *, world: int, global_batch: int, seq: int, lr: float,
               param_dtype) -> dict:
    """Rings at w=``world`` against psum; every reading is printed before
    any check, and the checks run together at the end."""
    import contextlib
    from unittest import mock

    import jax

    from repro.data.pipeline import SyntheticTokens
    from repro.models.model import build_model
    from repro.sched.backend import audit_compiled_step_cache
    from repro.training import train_step
    from repro.training.elastic import ElasticTrainer, SlotPlan
    from repro.training.optimizer import make_optimizer

    model = build_model(cfg)
    devices = jax.devices()[:world]
    results = []   # (ok, what), checked once every mode has run

    def verdict(ok: bool, what: str) -> None:
        results.append((bool(ok), what))

    p0 = None
    runs = {}
    out = {"world": world, "global_batch": global_batch, "seq": seq}
    for name in ("psum", "compressed-fused", "ring", FAULT):
        fault = name == FAULT
        patch = (mock.patch.dict(train_step.RING_MODES, {"ring": _local_only})
                 if fault else contextlib.nullcontext())
        with patch:
            trainer = ElasticTrainer(
                model, make_optimizer(cfg.optimizer),
                SyntheticTokens(cfg.vocab, seq, global_batch, seed=0),
                global_batch=global_batch, base_lr=lr,
                mode="ring" if fault else name, param_dtype=param_dtype)
            if p0 is None:
                p0 = [np.asarray(x, np.float32)
                      for x in jax.tree.leaves(trainer.params)]
            t0 = time.perf_counter()
            res = trainer.run_slot(SlotPlan(workers=world, steps=RING_STEPS))
            jax.block_until_ready(trainer.params)
            seconds = time.perf_counter() - t0
        prog = trainer.group._current
        leaf = jax.tree.leaves(trainer.params)[0]
        runs[name] = {
            "losses": list(trainer.losses),
            "moved": [np.asarray(x, np.float32) - a for x, a in
                      zip(jax.tree.leaves(trainer.params), p0)],
        }
        out[name] = {
            "workers": len(leaf.sharding.device_set),
            "losses": runs[name]["losses"],
            "slot_seconds_incl_compile": seconds,
            "warm_step_seconds": res["timings"].get(world),
        }
        if name == "compressed-fused":
            # the step as the group compiled it, lowered from shapes alone
            def shaped(tree, sharding):
                return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=sharding), tree)

            out[name]["tpu_custom_calls"] = prog.step_fn.lower(
                shaped(trainer.params, prog.replicated),
                shaped(trainer.opt_state, prog.replicated),
                shaped(trainer.data.batch(0), prog.batch_sharding),
            ).as_text().count("tpu_custom_call")
        print(json.dumps({name: out[name]}), flush=True)
        if not fault:
            verdict(list(prog.mesh.devices.flat) == devices
                    and set(leaf.sharding.device_set) == set(devices),
                    f"{name}: ring spans jax.devices()[:{world}]")
            verdict(all(math.isfinite(x) for x in trainer.losses),
                    f"{name}: losses finite")
        if name == "compressed-fused":
            verdict(out[name]["tpu_custom_calls"] > 0,
                    "compressed-fused: Pallas wire kernels lowered as "
                    "tpu_custom_call")
        if name == "ring":
            # mid-slot departure of half the ring: the survivors re-ring
            # and finish the slot, with no checkpoint restore
            t0 = time.perf_counter()
            res = trainer.run_slot(SlotPlan(workers=world, steps=2,
                                            leave=(1, world // 2)))
            jax.block_until_ready(trainer.params)
            leaf = jax.tree.leaves(trainer.params)[0]
            out["re_ring"] = {
                "slot_seconds_incl_compile": time.perf_counter() - t0,
                "losses": trainer.losses[RING_STEPS:],
                "re_rings": res["re_rings"], "restores": trainer.restores,
                "compile_count": trainer.group.compile_count,
                "final_devices": len(leaf.sharding.device_set),
            }
            print(json.dumps({"re_ring": out["re_ring"]}), flush=True)
            verdict(res["re_rings"] == 1 and trainer.restores == 0,
                    f"re-ring {world}->{world // 2} without a restore")
            verdict(len(leaf.sharding.device_set) == world // 2,
                    f"state lives on {world // 2} chips after the re-ring")
            verdict(all(math.isfinite(x) for x in trainer.losses),
                    "losses finite across the re-ring")
            verdict(trainer.group.compile_count == 2
                    and not audit_compiled_step_cache(trainer.group),
                    "one compile per ring size, cache audit clean")
        del trainer, leaf, prog
        gc.collect()

    ref = runs["psum"]
    ref_norm = math.sqrt(sum(float(np.sum(d * d)) for d in ref["moved"]))
    errs = {}
    for name in (*RING_TOL, FAULT):
        errs[name] = (
            max(abs(a - b) for a, b in
                zip(runs[name]["losses"], ref["losses"])),
            math.sqrt(sum(float(np.sum((a - b) ** 2)) for a, b in
                          zip(runs[name]["moved"], ref["moved"]))) / ref_norm)
        print(json.dumps({name: {"loss_err_vs_psum": errs[name][0],
                                 "param_update_rel_err_vs_psum":
                                     errs[name][1]}}), flush=True)
    out["errors_vs_psum"] = errs
    for name, (loss_tol, param_tol) in RING_TOL.items():
        loss_err, param_err = errs[name]
        verdict(loss_err <= loss_tol,
                f"{name}: losses within {loss_tol} of psum ({loss_err:.5f})")
        verdict(param_err <= param_tol,
                f"{name}: params moved within {param_tol} (relative) of "
                f"psum ({param_err:.5f})")
        verdict(errs[FAULT][0] > loss_tol or errs[FAULT][1] > param_tol,
                f"{name}'s tolerance rejects the planted {FAULT} fault "
                f"({errs[FAULT][0]:.5f}, {errs[FAULT][1]:.5f})")
    out["peak_bytes_per_device"] = [peak_bytes(d) for d in devices]
    print(json.dumps({"peak_bytes_per_device":
                      out["peak_bytes_per_device"]}), flush=True)
    verdict(all(b > 0 for b in out["peak_bytes_per_device"]),
            f"all {world} chips held state")
    for ok, what in results:
        print(f"  {'ok' if ok else 'FAILED'}: {what}", flush=True)
    failed = [what for ok, what in results if not ok]
    check(not failed, f"every four-chip check passed ({len(failed)} failed)")
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: train + serve phases; 4: ring phase on a 2x2")
    args = p.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform!r}, "
              f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
              "refusing to run on another backend", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(json.dumps({
        "jax": jax.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "device_kind": dev.device_kind, "device_count": len(devices),
    }), flush=True)

    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.launch.runtime import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    cfg = get_arch(ARCH)
    if args.chips == 1:
        train_phase(cfg, global_batch=8, seq=1024, slots=3,
                    steps_per_slot=3, lr=1e-3, param_dtype=jnp.bfloat16)
        gc.collect()
        serve_phase(cfg, n_requests=6, prompt_len=128, max_new=32,
                    max_batch=8, prefill_chunk=32, param_dtype=jnp.bfloat16)
    else:
        ring_phase(cfg, world=4, global_batch=8, seq=1024, lr=1e-3,
                   param_dtype=jnp.bfloat16)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
