"""The absorb kernel's roofline share stays at or under 100% for a kernel
whose time equals the model's bound, and the bound is the bytes one at
the cells' shapes."""
from __future__ import annotations

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _metric(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PEAK_F, PEAK_B = 197e12, 819e9


@pytest.mark.parametrize("tables,F,C,rows", [(16 * 1023, 10, 64, 16 * 4096),
                                             (63, 10, 16, 512),
                                             (1, 1, 2, 1)])
def test_share_at_the_bound_is_100(tables, F, C, rows):
    m = _metric("qo_update_leaves_roofline")
    flops, bytes_ = m.necessary_work(tables, F, C, rows)
    for calls in (1, 7):
        least = calls * max(flops / PEAK_F, bytes_ / PEAK_B)
        assert m.share(least, calls, tables, F, C, rows, PEAK_F, PEAK_B) \
            == pytest.approx(100.0)
        assert m.share(2 * least, calls, tables, F, C, rows, PEAK_F,
                       PEAK_B) <= 100.0


def test_cell_shape_is_bound_by_bytes():
    m = _metric("qo_update_leaves_roofline")
    flops, bytes_ = m.necessary_work(16 * 1023, 10, 64, 16 * 4096)
    assert bytes_ / PEAK_B > flops / PEAK_F
    # one pass over the four table planes, read and written, plus rows
    assert bytes_ == 4 * (16 * 4096 * 13 + 8 * 16 * 1023 * 10 * 64)


def test_reader_returns_nothing_without_the_kernel():
    m = _metric("qo_update_leaves_roofline")

    class Red:
        kernel_calls = {}

        def kernel_seconds(self, name):
            return 0.0

    assert m.read({"trace": Red()}) is None
    assert m.read({}) is None
