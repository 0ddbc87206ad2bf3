"""Host time inside ``ServingEngine.admit`` (which ends in a host sync)
over the prompt tokens it admitted, in milliseconds per token."""


def read(run, peaks):
    n = run.counters.get("prompt_tokens_admitted")
    if not n:
        return None
    return 1e3 * run.counters["admit_seconds"] / n
