"""The decode step's share of the chip's peak, in percent, read against
the roofline: for the traced decode steps, the least time the chip could
take for the work the live lanes need (``bench/flops.py``: their FLOPs at
the bf16 peak, or their bytes at the HBM bandwidth, whichever is longer;
bytes are the weights read once, the live positions' keys and values read
and the new ones written), over the device time of the programs that ran
inside the host's decode spans."""

import devtrace
import flops


def read(run, peaks):
    if not run.device_trace:
        return None
    dev = devtrace.device_seconds_per_span(run.device_trace, "decode")
    steps = run.info.get("traced_decode_positions") or []
    if not dev or len(dev) != len(steps) or not sum(dev) > 0:
        return None
    best = 0.0
    for positions in steps:
        w = flops.decode_step_work(run.config, positions)
        best += flops.roofline_seconds(w["flops"], w["bytes"], peaks)
    return 100.0 * best / sum(dev)
