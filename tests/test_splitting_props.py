"""Hypothesis property tests for wave-aware smart-splitting (paper §3.1.1).

Skipped entirely when hypothesis is not installed; the deterministic
counterparts in test_splitting.py always run."""
import math

import pytest

hyp = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.splitting import (naive_split, pad_to_multiple,  # noqa: E402
                                  smart_split, split_sizes_for_batch,
                                  wave_count)


@given(n=st.integers(1, 10_000_000), unit=st.integers(1, 4096))
@settings(max_examples=300, deadline=None)
def test_smart_split_invariants(n, unit):
    s = smart_split(n, unit)
    if s is None:
        assert n < 2 * unit
        return
    l1, l2 = s
    assert l1 + l2 == n
    assert l1 > 0 and l2 > 0
    # prefix split is full waves only
    assert l1 % unit == 0
    # the paper's wave-conservation property
    assert wave_count(l1, unit) + wave_count(l2, unit) == wave_count(n, unit)


@given(n=st.integers(2, 1_000_000), unit=st.integers(1, 2048))
@settings(max_examples=200, deadline=None)
def test_naive_split_can_add_waves_smart_never(n, unit):
    e1, e2 = naive_split(n)
    naive_waves = wave_count(e1, unit) + wave_count(e2, unit)
    assert naive_waves >= wave_count(n, unit)  # never fewer
    s = smart_split(n, unit)
    if s is not None:
        l1, l2 = s
        assert wave_count(l1, unit) + wave_count(l2, unit) <= naive_waves


@given(n=st.integers(1, 500_000), unit=st.integers(8, 512),
       rows=st.integers(1, 64), min_tokens=st.integers(0, 4096))
@settings(max_examples=200, deadline=None)
def test_split_sizes_for_batch(n, unit, rows, min_tokens):
    s = split_sizes_for_batch(n, unit=unit, min_tokens=min_tokens,
                              row_multiple=rows)
    if s is None:
        return
    l1, l2 = s
    assert l1 + l2 == n
    assert l1 % math.lcm(unit, rows) == 0
    assert n >= min_tokens


@given(n=st.integers(0, 1_000_000), m=st.integers(1, 4096))
@settings(max_examples=100, deadline=None)
def test_pad_to_multiple(n, m):
    p = pad_to_multiple(n, m)
    assert p >= n and p % m == 0 and p - n < m
