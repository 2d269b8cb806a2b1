"""Bucket ladder / physical repacking properties."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.repack import (
    bucket_ladder, expected_token_savings, pick_bucket, plan_microbatches,
)


def test_ladder_alignment():
    lad = bucket_ladder(4096, num_buckets=4, align=128)
    assert all(l % 128 == 0 for l in lad)
    assert lad[-1] >= 4096
    assert lad == tuple(sorted(set(lad)))


@settings(max_examples=50, deadline=None)
@given(max_len=st.integers(64, 8192), need=st.integers(1, 8192))
def test_pick_bucket_covers(max_len, need):
    lad = bucket_ladder(max_len, 4, 64)
    b = pick_bucket(min(need, max_len), lad)
    assert b >= min(need, max_len) or b == lad[-1]


def test_plan_microbatches_sorted_buckets():
    keep = np.array([100, 900, 50, 800, 120, 60, 70, 1000])
    plans = plan_microbatches(keep, 4, bucket_ladder(1024, 4, 64))
    # all rows covered exactly once
    rows = np.sort(np.concatenate([p.row_order for p in plans]))
    np.testing.assert_array_equal(rows, np.arange(8))
    # long rows grouped first -> later plans get smaller buckets
    lens = [p.bucket_len for p in plans]
    assert lens == sorted(lens, reverse=True)
    # each plan's bucket covers its rows
    for p in plans:
        assert keep[p.row_order].max() <= p.bucket_len


def test_expected_token_savings_formula():
    lengths = np.array([100, 200, 400])
    # E[kept per row] = (C + T)/2
    expect = ((8 + lengths) / 2).sum() / lengths.sum()
    got = expected_token_savings(lengths, min_cut=8)
    np.testing.assert_allclose(got, expect, rtol=1e-9)
    assert 0.5 < got < 0.55


def test_pick_bucket_overflow_raises():
    """Regression (ISSUE 4): needed > ladder[-1] used to silently return the
    last bucket, truncating kept tokens; it must be a hard error."""
    lad = bucket_ladder(256, num_buckets=4, align=64)
    import pytest

    with pytest.raises(ValueError, match="exceeds the bucket ladder"):
        pick_bucket(lad[-1] + 1, lad)
    # boundary: exactly the top bucket is fine
    assert pick_bucket(lad[-1], lad) == lad[-1]


def test_plan_microbatches_all_equal_lengths():
    keep = np.full(8, 100)
    plans = plan_microbatches(keep, 4, bucket_ladder(256, 4, 64))
    assert all(p.bucket_len == plans[0].bucket_len for p in plans)
    rows = np.sort(np.concatenate([p.row_order for p in plans]))
    np.testing.assert_array_equal(rows, np.arange(8))


def test_plan_microbatches_single_row():
    plans = plan_microbatches(np.array([37]), 1, bucket_ladder(128, 4, 32))
    assert len(plans) == 1
    np.testing.assert_array_equal(plans[0].row_order, [0])
    assert plans[0].bucket_len >= 37


def test_plan_microbatches_zero_keep_rows():
    """keep_len == 0 rows (nothing selected) still land in exactly one
    microbatch, padded to the smallest bucket."""
    keep = np.array([0, 0, 0, 0, 90, 80, 10, 0])
    ladder = bucket_ladder(128, 4, 32)
    plans = plan_microbatches(keep, 4, ladder)
    rows = np.sort(np.concatenate([p.row_order for p in plans]))
    np.testing.assert_array_equal(rows, np.arange(8))
    # the all-zero microbatches sit in the smallest bucket
    assert plans[-1].bucket_len == ladder[0]
    for p in plans:
        assert keep[p.row_order].max(initial=0) <= p.bucket_len


@settings(max_examples=50, deadline=None)
@given(
    lens=st.lists(st.integers(0, 512), min_size=1, max_size=32),
    nmb=st.integers(1, 8),
)
def test_plan_microbatches_unions_partition_batch(lens, nmb):
    """Property: microbatch row sets are disjoint and their union is the
    whole batch, for every divisible split."""
    keep = np.asarray(lens)
    if len(keep) % nmb:
        nmb = 1
    plans = plan_microbatches(keep, nmb, bucket_ladder(512, 4, 64))
    all_rows = np.concatenate([p.row_order for p in plans])
    assert len(all_rows) == len(set(all_rows.tolist())) == len(keep)
    np.testing.assert_array_equal(np.sort(all_rows), np.arange(len(keep)))
    for p in plans:
        if len(p.row_order):
            assert keep[p.row_order].max() <= p.bucket_len
