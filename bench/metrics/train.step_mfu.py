"""Model FLOP/s utilization of the traced window: the FLOPs forward and
backward need per token (``bench/flops.py``; recomputation not counted)
times tokens per second, over the chip's bf16 peak, in percent."""


def read(run, peaks):
    if "tokens" not in run.counters or not run.window_s > 0:
        return None
    rate = run.counters["tokens"] / run.window_s
    return 100.0 * rate * run.counters["flops_per_token"] / peaks["bf16_flops"]
