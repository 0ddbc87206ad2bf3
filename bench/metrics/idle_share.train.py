"""Share of the traced window in which no operation ran on the chip, in
percent (1 - busy / window, busy being the union of the device's operation
intervals)."""


def read(run, peaks):
    t = run.device_trace
    if not t or not t["window_s"] > 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
