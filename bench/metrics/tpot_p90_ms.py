"""90th percentile (nearest rank) over every request due in the window of
its time per output token: (last token - first token) / (tokens - 1), each
token stamped when the call that produced it returned; a request that
never finished counts as slower than all the others."""

import harness


def read(run, peaks):
    xs = run.samples.get("tpot_s")
    if not xs:
        return None
    return 1e3 * harness.percentile(xs, 90)
