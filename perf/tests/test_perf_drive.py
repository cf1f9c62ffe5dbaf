"""The window's end-to-end arithmetic on hand-made request records."""
import types

import numpy as np
import pytest

from perf import drive
from perf.generate import Req


def _rec(due, times, plen=4):
    req = types.SimpleNamespace(token_times=list(times))
    return drive.Record(Req(0, np.zeros(plen, np.int32), len(times), None),
                        0, due, due, req)


def test_window_metrics_by_hand():
    recs = [
        _rec(9.0, [9.5, 10.5, 11.0]),       # due before the window
        _rec(10.0, [10.2, 10.4, 10.6]),     # ttft 0.2
        _rec(12.0, [13.0, 21.0]),           # ttft 1.0, second token late
        _rec(19.0, []),                     # no token by the end: 1.0
    ]
    w = drive.window_metrics(recs, 10.0, 20.0)
    assert w["due"] == 3
    assert sorted(w["ttft_s"]) == pytest.approx([0.2, 1.0, 1.0])
    assert sorted(w["gaps_s"]) == pytest.approx([0.2, 0.2, 0.5])
    assert w["tokens"] == 6
    e = drive.end_to_end(w)
    assert e["tokens_per_s"] == pytest.approx(0.6)
    assert e["itl_p50_ms"] == pytest.approx(200.0)
    assert e["ttft_p95_ms"] == pytest.approx(1000.0)


def test_percentile_interpolates():
    assert drive.percentile([1, 2, 3, 4, 5], 0.5) == 3
    assert drive.percentile([0, 10], 0.95) == pytest.approx(9.5)
    with pytest.raises(ValueError):
        drive.percentile([], 0.5)
