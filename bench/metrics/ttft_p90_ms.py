"""90th percentile (nearest rank) over every request due in the window of
the time from when it was due to its first token; a request that never got
one counts as slower than all the others."""

import harness


def read(run, peaks):
    xs = run.samples.get("ttft_s")
    if not xs:
        return None
    return 1e3 * harness.percentile(xs, 90)
