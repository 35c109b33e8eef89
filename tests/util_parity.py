"""Shared float-tolerant parity comparison.

The device path computes DOUBLE attributes in float32 (tpu/dtypes.py policy:
TPU has no native f64), while the host oracle keeps Python float64 — parity
asserts therefore compare floats with f32-scale relative tolerance.
"""

import math


def rows_equal(e, a, rel=1e-5, abs_=1e-5):
    if len(e) != len(a):
        return False
    for x, y in zip(e, a):
        if isinstance(x, float) or isinstance(y, float):
            if x is None or y is None:
                if x is not y:
                    return False
            elif not math.isclose(float(x), float(y), rel_tol=rel, abs_tol=abs_):
                return False
        elif x != y:
            return False
    return True


def _sort_key(row):
    return tuple(
        (1, 0) if v is None else
        (0, round(v, 3)) if isinstance(v, float) else (0, v)
        for v in row)


def assert_rows_match(expected, actual, rel=1e-5, abs_=1e-5):
    """Order-insensitive multiset comparison with float tolerance."""
    exp = sorted(map(tuple, expected), key=_sort_key)
    act = sorted(map(tuple, actual), key=_sort_key)
    assert len(exp) == len(act), \
        f"row counts differ: oracle={len(exp)} device={len(act)}\n" \
        f"oracle[:5]={exp[:5]}\ndevice[:5]={act[:5]}"
    # rounding-keyed sort makes near-equal rows line up; fall back to greedy
    # matching only if the strict zip fails (ties ordered differently)
    if all(rows_equal(e, a, rel, abs_) for e, a in zip(exp, act)):
        return
    remaining = list(act)
    for e in exp:
        for i, a in enumerate(remaining):
            if rows_equal(e, a, rel, abs_):
                del remaining[i]
                break
        else:
            raise AssertionError(f"oracle row {e} has no device match; "
                                 f"unmatched device rows: {remaining[:5]}")


def assert_same_chunk(a, b):
    """Two ``ColumnsOut`` chunks element for element, in order, dtypes and
    NULL masks included."""
    import numpy as np
    assert len(a) == len(b) and list(a.cols) == list(b.cols)
    for name in a.cols:
        if len(a):
            assert a.cols[name].dtype == b.cols[name].dtype, name
        assert np.array_equal(a.cols[name], b.cols[name]), name
    assert (a.nulls is None) == (b.nulls is None)
    for name in a.nulls or {}:
        assert np.array_equal(a.nulls[name], b.nulls[name]), name
