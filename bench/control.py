"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 20]
                             [--stand-in fp8|half_batch]

For each seed the cell's driver runs a short window and then judges, by the
cell's own limits, a stand-in in the program's place: the plain reference
in float8 (``fp8``, the control: the next precision below the
configuration's bfloat16), or for a training cell the reference with half
of each batch left out (``half_batch``, a planted fault). It prints
``correct`` as the result line has it, with each compared number beside
its limit. A serving cell also prints the program's own widest logit gap
on the same served requests.

The benchmark's own runs never run this; ``bench/tests`` keeps both at a
small size.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
sys.path.insert(0, BENCH_DIR)

import cell  # noqa: E402
import harness  # noqa: E402


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20)
    p.add_argument("--stand-in", default="fp8",
                   choices=("fp8", "half_batch"))
    args = p.parse_args()
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    devices = harness.require_chips(1)
    harness.enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = cell.make_run(spec, args.workload, seed, args.seconds, False,
                          time.perf_counter())
        r.counter = harness.CompileCounter().install()
        kind = r.traffic["kind"]
        driver = cell.load_module("drivers", kind)
        if kind == "train":
            stand_in = ({"quant": "fp8"} if args.stand_in == "fp8" else
                        {"rows": r.traffic["global_batch"] // 2})
        elif args.stand_in == "fp8":
            stand_in = "fp8"
        else:
            raise SystemExit(f"no {args.stand_in} stand-in for {kind}")
        t0 = time.perf_counter()
        driver.run(r, devices, stand_in=stand_in)
        line = json.loads(harness.result_line(r, {}, {}, None))
        out = {"seed": seed, "stand_in": args.stand_in,
               "correct": line["correct"], "compared": line["compared"],
               "seconds": time.perf_counter() - t0}
        if kind == "serve":
            served = r.info["served"]
            out["program_gap"] = max(driver.token_gaps(
                r.config, harness.key_from_seed(seed), served,
                r.traffic["max_seq"]))
            out["checked_tokens"] = sum(len(t) for _, t in served)
        print(json.dumps(out, default=float), flush=True)


if __name__ == "__main__":
    main()
