"""Training tokens completed in the window over the window's seconds; slot
boundaries, scheduler time and per-step syncs are all inside it."""


def read(run, peaks):
    if "tokens" not in run.counters:
        return None
    return run.counters["tokens"] / run.window_s
