"""The benchmark's own arithmetic: peaks, FLOP counts, traffic, the trace
reduction."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops as F
from bench import harness as H
from bench import peaks
from bench import trace as T
from bench import traffic as TR

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=192, vocab_size=256,
             num_hidden_layers=2, rope_theta=1e6, rms_norm_eps=1e-6)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


def test_flops_match_a_hand_count():
    fam = H._load_module(str(ROOT / "bench/models/dense_gqa.py"))
    z = fam.dims(SMALL)
    # per layer: q and o 64*4*16 each, k and v 64*2*16 each, MLP 3*64*192
    per_layer = 2 * 4096 + 2 * 2048 + 3 * 12288
    n = 2 * per_layer + 64 * 256
    assert z["n"] == n
    attn = 4 * 4 * 16 * 2          # per key attended, over both layers
    assert z["attn"] == attn
    # one prompt of 3 tokens (positions 0..2) and its kept response of 2
    # tokens (positions 3, 4), a group of one
    want = 2 * n * 5 + attn * (1 + 2 + 3 + 4 + 5)
    assert F.rollout_flops(z, [3], [2], group=1) == want
    # a hull of 4 tokens: 6N per token, three times the attention term
    assert F.learner_flops(z, [4]) == 6 * n * 4 + 3 * attn * (
        1 + 2 + 3 + 4)
    # prompts are counted once per group
    two = F.rollout_flops(z, [3, 3], [2, 2], group=2)
    assert two == want + 2 * n * 2 + attn * (4 + 5)


def test_hull_is_one_past_the_last_kept_token():
    keep = np.zeros((3, 8), bool)
    keep[0, 2:5] = True
    keep[1, 7] = True
    assert F.hull_lengths(keep).tolist() == [5, 8, 0]


def _mix():
    return json.loads((ROOT / "bench/traffic/cot_rpc.json").read_text())


def test_traffic_is_fixed_by_the_seed():
    mix = _mix()
    a, b = TR.Traffic(mix, 2**33 + 7), TR.Traffic(mix, 2**33 + 7)
    for step in range(4):
        assert np.array_equal(a.budgets(step), b.budgets(step))
    toks = np.arange(40) % 23
    assert TR.reward(5, 0.5, toks) == TR.reward(5, 0.5, toks.copy())
    draws = [TR.reward(5, 0.5, toks + i) for i in range(400)]
    assert 0.4 < np.mean(draws) < 0.6


def test_every_seed_does_the_same_work_in_another_order():
    mix = _mix()
    lengths = mix["lengths"]
    p, g, gp = TR.group_sizes(mix["trainer"])
    ref = np.sort(TR.budget_table(mix), axis=1)
    orders = set()
    for seed in (1, 2, 3):
        t = TR.Traffic(mix, seed)
        for step in range(3):
            b = t.budgets(step).reshape(p, gp)
            assert b.min() >= lengths["min"] and b.max() <= lengths["max"]
            got = np.sort(b, axis=1)
            assert sorted(map(tuple, got)) == sorted(map(tuple, ref))
            orders.add(tuple(t.budgets(step)))
    assert len(orders) == 9


def test_trace_reduction_of_a_recorded_trace():
    red = T.reduce(str(DATA / "small.xplane.pb"))
    assert 0 < red["busy_s"] < red["window_s"]
    # three sleeps of 20 ms inside bench.select are idle time on the device
    gaps = dict(red["idle_gaps"])
    assert gaps.get("bench.select", 0) >= 0.055
    assert max(gaps, key=gaps.get) == "bench.select"
    assert red["device_ops"] and all(s > 0 for _, s in red["device_ops"])
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_union_and_clip():
    merged = T._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert T._clip(merged, 2, 6) == [(2, 3), (5, 6)]
