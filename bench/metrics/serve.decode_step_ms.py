"""Mean host time of ``ServingEngine.step`` (which ends in a host sync)
over the loop's decode steps, in milliseconds."""


def read(run, peaks):
    xs = run.samples.get("decode_step_s")
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
