"""The traffic generator: the same seed repeats bit for bit, another seed
differs, and a row does not depend on how its block was cut."""

from __future__ import annotations

import numpy as np

from chipbench import traffic_gen


def test_token_rows_repeat_bit_for_bit_and_differ_across_seeds():
    a = traffic_gen.token_rows(range(6), 4, 16, 512)
    b = traffic_gen.token_rows(range(6), 4, 16, 512)
    assert a.dtype == np.int32 and a.shape == (6, 17)
    assert (a == b).all()
    assert (a != traffic_gen.token_rows(range(6), 5, 16, 512)).any()
    assert a.min() >= 0 and a.max() < 512
    assert len({r.tobytes() for r in a}) == 6          # rows differ


def test_token_rows_do_not_depend_on_block_cuts():
    whole = traffic_gen.token_rows(range(6), 4, 16, 512)
    parts = np.concatenate([traffic_gen.token_rows([0, 1], 4, 16, 512),
                            traffic_gen.token_rows([2, 3, 4, 5], 4, 16, 512)])
    assert (whole == parts).all()


def test_streams_are_independent_of_one_another():
    a = traffic_gen.rng(3, traffic_gen.S_ROWS, 0).integers(0, 1 << 30, 4)
    b = traffic_gen.rng(3, traffic_gen.S_ROWS, 1).integers(0, 1 << 30, 4)
    c = traffic_gen.rng(3, traffic_gen.S_ROWS + 1, 0).integers(0, 1 << 30, 4)
    assert (a != b).any() and (a != c).any()
    assert (a == traffic_gen.rng(3, traffic_gen.S_ROWS, 0).integers(
        0, 1 << 30, 4)).all()
