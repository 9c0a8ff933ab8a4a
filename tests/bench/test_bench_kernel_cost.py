"""Bytes and operations of the victim-order kernel, from shapes."""
import json

import pytest

from bench import kernel_cost as K
from bench.cell import BENCH


def test_shapes_of_the_replay_universe():
    # the replay's 100 objects: one row of 128 lanes, one block
    assert K.victim_order_shapes(100) == dict(n=100, npad=128, grid=1,
                                              cand=8 * 128, top=8)
    s = K.victim_order_shapes(4608)
    # 36 rows of 128 lanes in blocks of 8 rows: 5 blocks, 40 rows
    assert s == dict(n=4608, npad=5120, grid=5, cand=5 * 8 * 128, top=8)


def test_bytes_and_ops():
    b, ops = K.victim_order_cost(4608)
    assert b == 5 * 5120 * 4 + 128 * 4 + 5120 * 4 + 2 * 5120 * 4
    assert ops == 5120 * (K.SCORE_OPS + 8 * K.ROUND_OPS)
    # a table smaller than one block is one padded block
    s = K.victim_order_shapes(100, top=4)
    assert (s["npad"], s["grid"]) == (128, 1)


def test_roofline_bound_on_v5e():
    peak = json.load(open(BENCH / "peaks.json"))["devices"]["TPU v5 lite"]
    t, bound = K.roofline_time(*K.victim_order_cost(4608), peak)
    assert bound == "bytes"
    assert t == pytest.approx(164352 / 819e9)
    t, bound = K.roofline_time(1.0, 1e15, peak)
    assert bound == "ops" and t == pytest.approx(1e15 / 197e12)
