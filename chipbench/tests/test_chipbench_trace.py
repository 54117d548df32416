"""The reduction from a profiler trace to metrics, and the operation and
byte counts, against hand counts."""

import dataclasses
import json
import pathlib

import pytest

import chipbench_smoke as smoke
from chipbench import common, flops, trace_reduce
from chipbench import run as harness

DATA = pathlib.Path(__file__).resolve().parent / "data"
CONFIGS = {n: json.loads((smoke.REPO / "chipbench" / "configs" / f"{n}.json").read_text())
           for n in ("minicpm2b", "granite8b_l16")}


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _ms(name, a, b):
    return Ev(name, a * 1e6, (b - a) * 1e6)


def synthetic():
    """Window 0..100 ms on two chips.  Chip 0: compute 0-40 and 50-60, a
    collective 30-55 (exposed 40-50), program A over 0-60.  Chip 1: compute
    0-80, program A over 0-80.  Host: a 'cb.wait' span over 60-100."""
    host = Plane("/host:CPU", [Line("python", [
        _ms("cb.window", 0, 100), _ms("cb.step", 0, 5), _ms("cb.wait", 60, 100),
        _ms("not.ours", 0, 100)])])
    dev0 = Plane("/device:TPU:0", [
        Line("XLA Ops", [_ms("fusion.1", 0, 40), _ms("collective-permute-done.3", 30, 55),
                         _ms("fusion.1", 50, 60)]),
        Line("XLA Modules", [_ms("jit_train_step(7)", 0, 60)])])
    dev1 = Plane("/device:TPU:1", [
        Line("XLA Ops", [_ms("fusion.2", 0, 80)]),
        Line("XLA Modules", [_ms("jit_train_step(7)", 0, 80)])])
    return [host, dev0, dev1, Plane("/host:metadata", [])]


def test_reduction_of_a_synthetic_two_chip_trace():
    r = trace_reduce.reduce(synthetic())
    assert r.chips == 2
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx((0.060 + 0.080) / 2)
    assert r.idle_share == pytest.approx(1 - 0.07 / 0.1)
    assert r.collective_s == pytest.approx(0.025 / 2)
    assert r.exposed_collective_s == pytest.approx(0.010 / 2)
    assert r.module("train_step") == pytest.approx([1, 0.07])
    assert r.module("serve_step") is None
    names = [n for n, _ in r.top_ops]
    assert names[0] == "fusion.2" and set(names) == {
        "fusion.1", "fusion.2", "collective-permute-done.3"}
    assert r.top_ops[1][1] == pytest.approx(0.05 / 2)
    # idle: chip 0 60-100, chip 1 80-100; both under the host's wait span
    assert [g[0] for g in r.idle_gaps] == ["cb.wait", "cb.wait"]
    assert [g[1] for g in r.idle_gaps] == pytest.approx([0.04, 0.02])


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    assert trace_reduce.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert trace_reduce.subtract([[0, 4], [6, 8]], [[3, 7]]) == [[0, 3], [7, 8]]
    assert trace_reduce.clip([(0, 5), (8, 9)], 1, 8) == [[1, 5]]


def test_a_trace_without_window_or_chip_is_refused():
    planes = synthetic()
    with pytest.raises(ValueError, match="cb.window"):
        trace_reduce.reduce([p for p in planes if p.name != "/host:CPU"])
    with pytest.raises(ValueError, match="TPU"):
        trace_reduce.reduce([p for p in planes if "device" not in p.name])


def test_metric_readers_on_the_synthetic_trace():
    r = trace_reduce.reduce(synthetic())
    peaks = common.peaks("TPU v5 lite")
    c = CONFIGS["minicpm2b"]
    inputs = {"steps": 2, "flops_per_step": 1e12, "flops": 4e12, "bytes": 1e9}
    read = {n: harness.load_metric(smoke.REPO, n).read(r, inputs, peaks, c)
            for n in ("device_idle_share.train", "step_mfu.train", "device_idle_share.serve",
                      "step_mfu.serve", "serve_step_roofline", "collective_ms.train4")}
    assert read["device_idle_share.train"] == read["device_idle_share.serve"] == pytest.approx(30.0)
    assert read["step_mfu.train"] == pytest.approx(100 * 2e12 / (2 * 197e12 * 0.1))
    assert read["step_mfu.serve"] == pytest.approx(100 * 4e12 / (2 * 197e12 * 0.1))
    assert read["serve_step_roofline"] is None  # no serve_step program in this trace
    # chip 0's collective 30-55 ms, chip 1 none: per chip and step
    assert read["collective_ms.train4"] == pytest.approx(25 / 2 / 2)
    serving = synthetic()
    for plane in serving[1:3]:
        plane.lines[1].events[0].name = "jit_serve_step(3)"
    r = trace_reduce.reduce(serving)
    assert harness.load_metric(smoke.REPO, "serve_step_roofline").read(
        r, inputs, peaks, c) == pytest.approx(100 * 1e9 / (0.07 * 819e9))


RECORDED = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_reduction_of_a_trace_recorded_on_the_chip(path):
    """A short window recorded on a TPU v5e: device planes, op and program
    lines and the benchmark's host spans are where the reduction looks."""
    expect = json.loads(path.with_suffix("").with_suffix(".json").read_text())
    r = trace_reduce.reduce_file(str(path))
    assert r.chips == expect["chips"]
    assert 0 < r.busy_s <= r.window_s
    assert r.window_s == pytest.approx(expect["window_s"], rel=1e-6)
    assert r.busy_s == pytest.approx(expect["busy_s"], rel=1e-6)
    assert r.module(expect["program"])[0] == expect["executions"]
    assert r.collective_s == pytest.approx(expect["collective_s"], rel=1e-6, abs=1e-12)
    assert [g[0] for g in r.idle_gaps][:1] == expect["first_gap_label"]


def test_flops_and_bytes_against_hand_counts():
    c = CONFIGS["minicpm2b"]
    # one layer: q and o 2304 x (36 x 64) each, k and v 2304 x (36 x 64), SwiGLU 3 x 2304 x 5760
    assert flops.layer_matmul_params(c) == 4 * 2304 * 2304 + 3 * 2304 * 5760 == 61_046_784
    assert flops.unembed_params(c) == 2304 * 122_753
    assert flops.causal_pairs(2048) == 2048 * 2049 // 2
    # 40 layers, 2 rows of 2048: matrices forward, causal attention scores and mixing
    fwd = (2 * (40 * 61_046_784 + 282_822_912) * (2 * 2048)
           + 4 * 36 * 64 * (2 * 2048 * 2049 // 2) * 40)
    assert flops.train_step_flops(c, 2, 2048) == 3 * fwd == 71_602_916_032_512
    g = CONFIGS["granite8b_l16"]
    # GQA: k and v are 4096 x (8 x 128)
    assert flops.layer_matmul_params(g) == (2 * 4096 * 4096 + 2 * 4096 * 1024
                                            + 3 * 4096 * 14336)
    # serving wave: 256 + 32 positions through 16 layers, 32 tokens unembedded
    assert flops.wave_flops(g, 32, 256, 32) == 32 * (
        2 * 16 * 218_103_808 * 288 + 2 * 4096 * 49152 * 32) + 4 * 32 * 128 * 16 * 32 * (288 * 289 // 2)
    # the weights a decode step reads: 16 layers' matrices and norms, final norm, unembedding
    assert flops.weight_bytes(g) == 2 * (16 * (218_103_808 + 2 * 4096) + 4096 + 4096 * 49152)
    # one cache row: k and v, 16 layers, 32 sequences, 8 kv heads of 128, bf16
    assert flops.cache_row_bytes(g, 32) == 2 * 16 * 32 * 8 * 128 * 2 == 2_097_152
    # the step writing position 9 reads rows 0..9 and writes one
    assert flops.decode_step_bytes(g, 32, 9) == flops.weight_bytes(g) + 11 * 2_097_152
    assert flops.wave_bytes(g, 32, 3, 2) == sum(flops.decode_step_bytes(g, 32, p)
                                                for p in range(5))
