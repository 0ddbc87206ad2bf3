"""The yardstick's own arithmetic, and the comparison that decides
``correct``: sound runs pass, the control and each fault of the timed path
fail. Drivers run at a small size on the CPU with the chip check skipped."""

import json
import os
import subprocess
import sys

import pytest

import devtrace
import flops
import generator
import harness
import small

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def config(name):
    return harness.load_json(os.path.join(BENCH, "configs", name + ".json"))


def driver(kind):
    import cell

    return cell.load_module("drivers", kind)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def test_qwen3_training_flops_per_token():
    c = config("qwen3-0.6b")
    # by hand: per layer q 1024*16*128, k and v 1024*8*128 each, o as q,
    # MLP 3*1024*3072; 28 layers; lm_head 1024*151936
    layer = 2 * 1024 * 2048 + 2 * 1024 * 1024 + 3 * 1024 * 3072
    mm = 28 * layer + 1024 * 151936
    assert flops.matmul_params(c) == mm == 595_984_384
    attn = 4 * 16 * 128 * (1024 + 1) / 2 * 28
    assert flops.train_flops_per_token(c, 1024) == pytest.approx(
        3 * (2 * mm + attn))
    assert 3.85e9 < flops.train_flops_per_token(c, 1024) < 3.95e9


def test_decode_step_work_counts_live_lanes_only():
    c = dict(small.CONFIG)
    mm = flops.matmul_params(c)          # 2*(128*128+2*128*64+128*128...)
    w = flops.decode_step_work(c, [3, 0])
    keys = 4 + 1
    assert w["flops"] == 2 * mm * 2 + 4 * 4 * 32 * keys * 2
    kv_row = 2 * 2 * 32 * 2 * 2          # K and V, kv heads, head_dim, bf16
    assert w["bytes"] == (mm + 2 * 128) * 2 + kv_row * (keys - 2) \
        + kv_row * 2
    # a wider cache or more free lanes change nothing: only positions do
    assert flops.decode_step_work(c, [3, 0]) == w


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(200.0, 10.0, peaks) == 2.0
    assert flops.roofline_seconds(100.0, 30.0, peaks) == 3.0


def test_percentile_is_nearest_rank():
    xs = list(range(1, 11))
    assert harness.percentile(xs, 90) == 9
    assert harness.percentile(xs, 50) == 5
    assert harness.percentile([float("inf"), 1.0], 90) == float("inf")


def test_peaks_table_refuses_unknown_devices():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_trace_reduction_on_a_recorded_chip_trace():
    """A trace recorded on a v5e: five runs of one jitted program, then an
    eager multiply, inside a window laid over them by hand."""
    raw = devtrace.read(os.path.join(HERE, "data", "tiny.xplane.pb"))
    dev = raw["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 6 and len(dev["ops"]) == 23
    lo, hi = dev["modules"][0][1], dev["modules"][4][2]
    raw["spans"] = [("bench.window", lo, hi),
                    ("bench.step", lo - 10, dev["modules"][0][2] + 10)]
    out = devtrace.reduce(raw)
    merged = devtrace.union(devtrace.clip(
        [(s, e) for _, s, e in dev["ops"]], lo, hi))
    assert out["busy_s"] == pytest.approx(devtrace.total(merged) / 1e9)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < out["busy_s"] < out["window_s"]
    # the two fusions of the program carry nearly all the device time
    names = [n for n, _ in out["device_ops"]]
    assert names[:2] == ["convolution_tanh_fusion", "fusion"]
    assert sum(g for _, g in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    per = devtrace.device_seconds_per_span(out, "step")
    m0 = dev["modules"][0]
    assert per == [pytest.approx(devtrace.total(devtrace.union(
        devtrace.clip([(s, e) for _, s, e in dev["ops"]], m0[1], m0[2])))
        / 1e9)]
    assert 0.9 * (m0[2] - m0[1]) / 1e9 < per[0] <= (m0[2] - m0[1]) / 1e9


def test_back_to_back_spans_share_no_program():
    """Spans that run back to back, each a little before its program (the
    programs are ~3 ms apart): every execution is counted once, in its own
    span, and the spans' device time is no more than the busy time."""
    raw = devtrace.read(os.path.join(HERE, "data", "tiny.xplane.pb"))
    mods = raw["devices"]["/device:TPU:0"]["modules"]
    lead = 500_000
    lo, hi = mods[0][1] - lead, mods[4][2] + 10
    edges = [m[1] - lead for m in mods[:5]] + [hi]
    raw["spans"] = [("bench.window", lo, hi)] + [
        ("bench.step", edges[k], edges[k + 1]) for k in range(5)]
    out = devtrace.reduce(raw)
    per = devtrace.device_seconds_per_span(out, "step")
    ops = [(s, e) for _, s, e in raw["devices"]["/device:TPU:0"]["ops"]]
    assert per == [pytest.approx(devtrace.total(devtrace.union(
        devtrace.clip(ops, s, e))) / 1e9) for _, s, e in mods[:5]]
    assert sum(per) <= out["busy_s"]
    # the eager multiply after the last span belongs to no span
    assert sum(len(p) for p in out["programs_in"]["bench.step"]) == 5


def test_union_and_clip():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3),
                                                                (5, 9)]
    assert devtrace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_generator_gives_every_seed_the_same_work():
    mix = harness.load_json(os.path.join(BENCH, "traffic", "chat.json"))["mix"]
    a = generator.arrivals(mix, 1.0, 3, 51.0, 1000)
    b = generator.arrivals(mix, 1.0, 2 ** 31 + 17, 51.0, 1000)
    assert [(x.due_s, len(x.prompt), x.max_new, x.in_window) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new, x.in_window) for x in b]
    assert sum(x.in_window for x in a) > 30
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert all(4 <= len(x.prompt) <= 1024 and 8 <= x.max_new <= 1024
               for x in a)


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------

def _correct(r):
    line = json.loads(harness.result_line(r, {}, {}, None))
    return line["correct"], line["compared"]


def _keep_state(trainer):
    """A step that returns its state unchanged."""
    from repro.training.optimizer import Optimizer

    opt = trainer.group.optimizer
    trainer.group.optimizer = Optimizer(
        opt.name, opt.init, lambda g, s, p, lr: (p, s))


def _half_batch(trainer):
    """Half of the batch left out, the mean taken over the rest."""
    model = trainer.model
    loss = model.loss
    model.loss = lambda p, b: loss(
        p, {k: v[: v.shape[0] // 2] for k, v in b.items()})


@pytest.fixture(scope="module")
def jax_cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def test_training_matches_the_reference(jax_cpu):
    r = small.run(small.TRAIN, seconds=1.0)
    driver("train").run(r, jax_cpu.devices(), arch_override=small.arch())
    ok, compared = _correct(r)
    assert ok, compared
    assert r.counters["steps"] > 0 and r.counters["compile_events_in_window"] == 0


@pytest.mark.parametrize("fault", [_keep_state, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_training_faults_are_not_correct(jax_cpu, fault):
    r = small.run(small.TRAIN, seconds=0.5)
    driver("train").run(r, jax_cpu.devices(), arch_override=small.arch(),
                        break_step=fault)
    ok, compared = _correct(r)
    assert not ok, compared


def test_training_control_is_not_correct(jax_cpu):
    """The reference in float8 in the program's place."""
    r = small.run(small.TRAIN, seconds=0.5)
    driver("train").run(r, jax_cpu.devices(), arch_override=small.arch(),
                        stand_in={"quant": "fp8"})
    ok, compared = _correct(r)
    assert not ok, compared


def _alter_tokens(engine):
    """A token altered where it is produced."""
    decode = engine._decode
    vocab = engine.model.cfg.vocab

    def step(*args):
        nxt, cache = decode(*args)
        return (nxt + 1) % vocab, cache

    engine._decode = step


def _keep_cache(engine):
    """A decode step that returns its cache unchanged."""
    decode = engine._decode

    def step(params, cache, *rest):
        nxt, _ = decode(params, cache, *rest)
        return nxt, cache

    engine._decode = step


def test_serving_matches_the_reference(jax_cpu):
    r = small.run(small.SERVE, seconds=1.0)
    driver("serve").run(r, jax_cpu.devices(), arch_override=small.arch())
    ok, compared = _correct(r)
    assert ok, compared
    assert r.attempted > 5 and r.failed == 0


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_cache],
                         ids=["token_altered", "cache_unchanged"])
def test_serving_faults_are_not_correct(jax_cpu, fault):
    r = small.run(small.SERVE, seconds=0.5)
    driver("serve").run(r, jax_cpu.devices(), arch_override=small.arch(),
                        break_engine=fault)
    ok, compared = _correct(r)
    assert not ok, compared


def test_serving_control_is_not_correct(jax_cpu):
    """At each position of the served requests, the token the float8
    reference puts first, in the program's place."""
    r = small.run(small.SERVE, seconds=0.5)
    driver("serve").run(r, jax_cpu.devices(), arch_override=small.arch(),
                        stand_in="fp8")
    ok, compared = _correct(r)
    assert not ok, compared


def test_decode_mfu_reads_the_roofline_per_traced_step():
    import cell

    c = dict(small.CONFIG)
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    positions = [[3, 0], [4, 1, 7]]
    work = [flops.decode_step_work(c, p) for p in positions]
    best = sum(flops.roofline_seconds(w["flops"], w["bytes"], peaks)
               for w in work)
    r = small.run(small.SERVE)
    r.info["traced_decode_positions"] = positions
    r.device_trace = {"programs_in": {"bench.decode": [
        {"jit_step": 0.010}, {"jit_step": 0.012, "jit_other": 0.003}]}}
    mfu = cell.load_module("metrics", "serve.decode_mfu")
    assert mfu.read(r, peaks) == pytest.approx(100 * best / 0.025)
    # a step the trace and the loop do not agree on reads nothing
    r.info["traced_decode_positions"] = positions[:1]
    assert mfu.read(r, peaks) is None


def test_tpot_counts_an_unfinished_request_slowest():
    import cell

    r = small.run(small.SERVE)
    r.samples["tpot_s"] = [0.01 * k for k in range(1, 10)] + [float("inf")]
    tpot = cell.load_module("metrics", "tpot_p90_ms")
    assert tpot.read(r, {}) == pytest.approx(90.0)
    r.samples["tpot_s"][0] = float("inf")
    assert tpot.read(r, {}) == float("inf")


def test_cell_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "cell.py"), "--workload",
         "train.qwen3-0.6b.b8s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{\"correct\"")
    assert "no TPU" in p.stderr
