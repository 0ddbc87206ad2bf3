"""Per slot that ended in the window: ``schedule_slot`` time plus the part
of ``execute_slot`` not spent in train steps, mean in milliseconds."""


def read(run, peaks):
    xs = run.samples.get("slot_overhead_s")
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
