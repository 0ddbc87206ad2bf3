"""The program's spans in a trace: the exact split of the device's idle
time over the innermost span, the per-layer idle shares and the
host-to-device offset; each reads nothing where the program made no
span."""

import os

import pytest

import devtrace
import spans

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _sp(name, s, e, **ids):
    return (name, s * MS, e * MS, ids)


# a window of 110 ms: two slots' worth of host work, in milliseconds
HAND = [
    _sp("bench.window", 0, 110),
    _sp("bench.train_window", 0, 98),
    _sp("repro.slot", 10, 90, t=0),
    _sp("repro.sched.decide", 10, 20, t=0),
    _sp("repro.gadget.lp", 12, 18, jobs=1, candidates=3),
    _sp("repro.train.step", 30, 60, step=0, workers=1),
    _sp("repro.train.input", 30, 35),
    _sp("repro.train.dispatch", 35, 40),
    _sp("repro.train.sync", 40, 60),
    _sp("repro.train.step", 60, 85, step=1, workers=1),
    _sp("repro.slot.commit", 85, 90, t=0),
]
BUSY = [(5 * MS, 9 * MS), (13 * MS, 16 * MS), (38 * MS, 58 * MS),
        (62 * MS, 70 * MS), (69 * MS, 80 * MS)]
LO, HI = 0, 110 * MS


def test_idle_splits_exactly_over_the_innermost_span():
    got = spans.idle_by_span(BUSY, HAND, LO, HI)
    # idle: 0-5, 9-13, 16-38, 58-62, 80-110 ms
    assert got == pytest.approx({
        "bench.train_window": 0.014, "repro.gadget.lp": 0.003,
        "repro.sched.decide": 0.004, "repro.slot": 0.010,
        "repro.train.input": 0.005, "repro.train.dispatch": 0.003,
        "repro.train.sync": 0.002, "repro.train.step": 0.007,
        "repro.slot.commit": 0.005, spans.BETWEEN: 0.012,
    }, abs=1e-12)
    busy = devtrace.total(devtrace.union(BUSY))
    assert sum(got.values()) == pytest.approx((HI - LO - busy) / 1e9,
                                              abs=1e-12)


def test_timeline_covers_the_window_once():
    pieces = spans.timeline(HAND, LO, HI)
    assert pieces[0][0] == LO and pieces[-1][1] == HI
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    # a span and its first child start together: the child is inner
    assert [p[2] for p in pieces[:5]] == [
        "bench.train_window", "repro.sched.decide", "repro.gadget.lp",
        "repro.sched.decide", "repro.slot"]


def test_idle_shares_of_the_layers():
    got = spans.idle_shares(BUSY, HAND, LO, HI)
    # under the steps: 30-38, 58-62, 80-85; under the slot but no step:
    # 10-13, 16-30, 85-90
    assert got == pytest.approx({
        "idle_share.train.step": 100 * 17 / 110,
        "idle_share.train.slot_edge": 100 * 22 / 110})
    by = spans.idle_by_span(BUSY, HAND, LO, HI)
    step = sum(by[k] for k in ("repro.train.step", "repro.train.input",
                               "repro.train.dispatch", "repro.train.sync"))
    assert got["idle_share.train.step"] == pytest.approx(
        100 * step / 0.110)


def test_idle_gaps_are_named_by_the_innermost_span():
    gaps = spans.idle_gaps(BUSY, HAND, LO, HI, top=3)
    assert gaps == [[spans.BETWEEN, pytest.approx(0.030)],
                    ["repro.slot", pytest.approx(0.022)],
                    ["bench.train_window", pytest.approx(0.005)]]


def test_readers_find_nothing_without_the_programs_spans():
    bench_only = [sp for sp in HAND if sp[0].startswith("bench.")]
    assert spans.idle_shares(BUSY, bench_only, LO, HI) == {}
    assert spans.idle_under(BUSY, bench_only, LO, HI,
                            ("repro.serve.step",)) is None
    assert spans.dispatch_offsets(bench_only, [("p", 0, 1)],
                                  "repro.serve.step.dispatch") == []


def test_dispatch_offsets_on_a_recorded_chip_trace():
    raw = devtrace.read(os.path.join(HERE, "data", "tiny.xplane.pb"))
    mods = raw["devices"]["/device:TPU:0"]["modules"]
    # a host span ending 20 us after each program starts, one ending before
    hand = [("repro.serve.step.dispatch", m[1] - 50_000, m[1] + 20_000, {})
            for m in mods[:3]]
    hand.append(("repro.serve.step.dispatch", mods[3][1] - 90_000,
                 mods[3][1] - 40_000, {}))
    assert spans.dispatch_offsets(hand, mods,
                                  "repro.serve.step.dispatch") == [
        -20_000, -20_000, -20_000, 40_000]


def test_chip_trace_reduction_is_unchanged():
    """What ``devtrace.reduce`` gives on a recorded trace, pinned: a change
    that teaches it the program's spans must leave the inputs of the
    metrics that read it as they are. The program's split of the same
    window sums to the idle time ``reduce`` reads."""
    raw = devtrace.read(os.path.join(HERE, "data", "tiny.xplane.pb"))
    dev = raw["devices"]["/device:TPU:0"]
    lo, hi = dev["modules"][0][1], dev["modules"][4][2]
    raw["spans"] = [("bench.window", lo, hi),
                    ("bench.step", lo - 10, dev["modules"][0][2] + 10)]
    out = devtrace.reduce(raw)
    assert out["busy_s"] == pytest.approx(0.000136673, abs=1e-12)
    assert out["window_s"] == pytest.approx(0.012782409, abs=1e-12)
    assert out["device_ops"] == [
        ["convolution_tanh_fusion", pytest.approx(7.3518e-05, abs=1e-12)],
        ["fusion", pytest.approx(6.3078e-05, abs=1e-12)],
        ["copy-start", pytest.approx(6.5e-08, abs=1e-12)],
        ["copy-done", pytest.approx(1.2e-08, abs=1e-12)]]
    assert out["programs_in"] == {"bench.step": [
        {"jit__lambda(12027381475514182978)": pytest.approx(2.7364e-05,
                                                            abs=1e-12)}]}
    # the program's split of the same window sums to its idle time
    busy = [(s, e) for _, s, e in dev["ops"]]
    hand = [(n, s, e, {}) for n, s, e in raw["spans"]] + [
        ("repro.serve.step", m[1] - 1000, m[2] + 1000, {"lanes": 1})
        for m in dev["modules"][:5]]
    by = spans.idle_by_span(busy, hand, lo, hi)
    assert sum(by.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], abs=1e-12)


def test_spans_read_back_from_a_recorded_trace(tmp_path):
    import jax

    from repro.launch.runtime import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("slot", t=4):
            for k in range(2):
                with span("train.step", step=k, workers=1):
                    pass
    finally:
        jax.profiler.stop_trace()
    got = spans.read(devtrace.find_xplane(str(tmp_path)))
    assert [(n, ids) for n, _, _, ids in got] == [
        ("repro.slot", {"t": 4}),
        ("repro.train.step", {"step": 0, "workers": 1}),
        ("repro.train.step", {"step": 1, "workers": 1})]
    (slot_s, slot_e), steps = got[0][1:3], got[1:]
    assert all(slot_s <= s <= e <= slot_e for _, s, e, _ in steps)
