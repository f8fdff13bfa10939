"""The benchmark's yardstick on its own: traffic generation, work counts
against hand-worked shapes, and the trace reduction on a small recorded
trace."""
from __future__ import annotations

import dataclasses
import math

import pytest

import benchtree  # noqa: F401  (puts bench/ on the import path)
from harness import spec, traffic, work
from harness import trace as tr

SMOL = spec.load_cell("smollm-360m.coldstart")
YI = spec.load_cell("yi-9b-l24.batch")


def _take(seed: int, n: int = 128):
    s = traffic.stream(YI.traffic, seed, 64000)
    return [next(s) for _ in range(n)]


def test_every_seed_gets_the_same_work_in_another_order():
    """The same lengths in the same order for every seed (a window holds
    part of a block, so its work must not depend on the seed); the seed
    orders the token ids."""
    a, b = _take(1), _take(2**33 + 17)
    assert [(len(g.prompt), g.max_new) for g in a] == \
        [(len(g.prompt), g.max_new) for g in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    sizes = [(len(g.prompt), g.max_new) for g in a]
    assert sizes[:64] != sizes[64:]  # each block in an order of its own
    for k in (0, 1):  # each block the same prompt and output lengths
        assert sorted(x[k] for x in sizes[:64]) == \
            sorted(x[k] for x in sizes[64:])
    assert [g.prompt for g in _take(1)] == [g.prompt for g in a]
    firsts = [g.prompt[0] for g in a]
    assert len(set(firsts)) == len(firsts)  # nothing shared, not one token
    p = [len(g.prompt) for g in a]
    assert min(p) >= 64 and max(p) <= 2048
    o = [g.max_new for g in a]
    assert min(o) >= 16 and max(o) <= 512


def test_stratified_lengths_follow_the_distribution():
    q = traffic.quantiles({"dist": "lognormal", "median": 128, "sigma": 1.0,
                           "min": 16, "max": 1024}, 1001)
    assert q[500] == 128 and q == sorted(q)
    assert q[0] == 16 and q[-1] == 1024
    assert traffic.quantiles({"dist": "fixed", "value": 32}, 3) == [32] * 3
    probes = traffic.probes(SMOL.traffic, 2**33 + 5, 5, 49152)
    assert [(len(g.prompt), g.max_new) for g in probes] == [(32, 16)] * 5


def test_stream_blocks_hold_the_same_sizes():
    mix = YI.traffic
    s = traffic.stream(mix, 9, 64000)
    blocks = [[next(s) for _ in range(64)] for _ in range(2)]
    sizes = [sorted((len(g.prompt), g.max_new) for g in b) for b in blocks]
    assert sorted(x for x, _ in sizes[0]) == sorted(x for x, _ in sizes[1])


def test_work_counts_by_hand():
    d = SMOL.dims  # 32 layers, 960 wide, 15/5 heads of 64, 2560, 49152 tied
    per_layer = 960 * 960 + 2 * 960 * 320 + 960 * 960 + 3 * 960 * 2560
    assert per_layer == 9_830_400
    assert work.matmul_params(d) == 32 * per_layer + 960 * 49152
    # tied: the head is the embedding table, read once; norms 2 a layer + 1
    assert work.param_bytes(d) == 2 * (32 * per_layer + 65 * 960
                                       + 960 * 49152)
    assert work.kv_bytes_per_position(d) == 32 * 2 * 5 * 64 * 2 == 40960
    rows, ctx = 4, 1000
    assert work.step_flops(d, rows, ctx) == (
        2 * 4 * (32 * per_layer + 960 * 49152) + 4 * 32 * 15 * 64 * 1000)
    assert work.step_bytes(d, rows, ctx) == work.param_bytes(d) + 40960 * 1000
    y = YI.dims  # untied: the embedding rows of the batch are gathered
    assert work.step_bytes(y, 16, 0) == work.param_bytes(y) + 16 * 4096 * 2
    peak = spec.peaks("TPU v5 lite")
    # 24 layers of 173,015,040 weights, the head 4096 x 64000, bf16
    assert work.param_bytes(y) == 2 * (24 * 173_015_040 + 49 * 4096
                                       + 4096 * 64000)
    t, bound = work.least_time(y, peak, 16, 16 * 500)
    assert bound == "memory"
    assert t == pytest.approx(work.step_bytes(y, 16, 8000) / 819e9)
    t, bound = work.least_time(dataclasses.replace(y, layers=1), peak,
                               100_000, 0)
    assert bound == "compute"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v9000")


def _ev(plane, line, name, start_ms, dur_ms):
    return tr.Event(plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def test_reduction_of_a_hand_made_trace():
    dev, host = "/device:TPU:0", "/host:CPU"
    evs = [_ev(dev, tr.MODULE_LINE, "jit_decode_step", 0, 4),
           _ev(dev, tr.OP_LINE, "fusion.1", 0, 3),
           _ev(dev, tr.OP_LINE, "fusion.2", 2, 2),     # overlaps: union 0-4
           _ev(dev, tr.MODULE_LINE, "jit_decode_step", 10, 4),
           _ev(dev, tr.OP_LINE, "fusion.1", 10, 4),
           _ev(dev, tr.OP_LINE, "copy", 20, 1),
           _ev(host, "python3", "bench.step", 3, 9),   # covers gap 4-10
           _ev(host, "python3", "bench.submit", 15, 4)]  # covers gap 14-20
    s = tr.reduce(evs, window_s=0.025)
    assert s.devices == 1
    assert math.isclose(s.busy_s, 0.009)
    assert s.programs == {"jit_decode_step": (pytest.approx(0.008), 2)}
    assert s.program_s == pytest.approx(0.008)
    assert s.top_ops[0] == ("fusion.1", pytest.approx(0.007))
    assert sorted(g[0] for g in s.idle_gaps) == ["bench.step", "bench.submit"]
    assert [g[1] for g in s.idle_gaps] == [pytest.approx(0.006)] * 2
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"} and len(b["device_ops"]) <= 10
