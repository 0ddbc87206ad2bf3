"""Find a serving cell's knee once, on the chip: the highest arrival rate
its engine sustains without a growing backlog.

    python3 bench/knee.py --workload <serve cell> --rates 0.6,0.8,1.0 --seconds 40

Each rate runs the cell's driver with the cell's traffic at that rate, in
one process, and prints how many of the requests due in the window finished
within it, and the time to first token of the window's first and last
thirds: a backlog that grows shows as a last third far slower than the
first. It also compiles the engine's decode and prefill steps at
``--lanes`` for the chip and prints their memory, which is how a cell's
``max_batch`` is chosen. Nothing here is a metric of the benchmark.
"""

import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
sys.path.insert(0, BENCH_DIR)

import cell  # noqa: E402
import harness  # noqa: E402


def rehearse(r, lanes):
    """Bytes of the compiled decode and prefill steps at ``lanes`` lanes."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import ServingEngine

    import lm

    model = lm.program_model(r.config)
    params = model.abstract_params(dtype=jnp.bfloat16)
    out = {}
    for n in lanes:
        eng = ServingEngine.__new__(ServingEngine)
        eng.model, eng.compile_count, eng.prefill_compile_count = model, 0, 0
        cache = model.abstract_cache(n, r.traffic["max_seq"])
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        try:
            dec = jax.jit(eng._make_decode()).lower(
                params, cache, jax.ShapeDtypeStruct((n, 1), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.bool_)).compile()
            pre = jax.jit(eng._make_prefill()).lower(
                params, cache, i32, jax.ShapeDtypeStruct((1, 8), jnp.int32),
                i32, i32).compile()
        except jax.errors.JaxRuntimeError as e:   # does not fit the chip
            out[f"lanes@{n}"] = f"refused: {str(e)[:300]}"
            continue
        for name, comp in (("decode", dec), ("prefill", pre)):
            m = comp.memory_analysis()
            out[f"{name}@{n}"] = {
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "total_bytes": m.argument_size_in_bytes
                + m.output_size_in_bytes + m.temp_size_in_bytes
                - m.alias_size_in_bytes}
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=40)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--lanes", default="16,32")
    args = p.parse_args()
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    devices = harness.require_chips(1)
    harness.enable_compile_cache()
    driver = cell.load_module("drivers", "serve")
    r0 = cell.make_run(spec, args.workload, args.seed, args.seconds, False,
                       time.perf_counter())
    print(json.dumps({"rehearsal": rehearse(
        r0, [int(x) for x in args.lanes.split(",")])}), flush=True)
    for rate in [float(x) for x in args.rates.split(",")]:
        r = cell.make_run(spec, args.workload, args.seed, args.seconds,
                          False, time.perf_counter())
        r.traffic["rate_per_s"] = rate
        r.traffic["check"] = {"requests": 0}
        r.counter = harness.CompileCounter().install()
        driver.run(r, devices)
        ttft = r.samples["ttft_s"]
        third = max(1, len(ttft) // 3)
        print(json.dumps({
            "rate_per_s": rate, "due": r.attempted,
            "unfinished_at_drain_end": r.failed,
            "ttft_first_third_p50_s": harness.percentile(ttft[:third], 50),
            "ttft_last_third_p50_s": harness.percentile(ttft[-third:], 50),
            "ttft_p90_s": harness.percentile(ttft, 90),
            "tpot_p90_s": harness.percentile(r.samples["tpot_s"], 90),
            "decode_step_ms": 1e3 * sum(r.samples["decode_step_s"])
            / len(r.samples["decode_step_s"]),
            "prefill_ms_per_token": 1e3 * r.counters["admit_seconds"]
            / r.counters["prompt_tokens_admitted"]}), flush=True)
        del r
        gc.collect()


if __name__ == "__main__":
    main()
