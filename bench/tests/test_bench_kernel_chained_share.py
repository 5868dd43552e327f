"""The transform stage's ``kernel_chained_share`` reader on canned
``window_log`` records: stamped records read their share, and a program
whose records carry no ``kernel_chained`` stamp reads nothing."""
import types

import pytest

from bench.common import registry


def _run(windows):
    return types.SimpleNamespace(windows=windows)


def _window(seq, chained=None):
    record = {"seq": seq, "key": "k", "size": 3, "arrival_wait_ms": 6.0,
              "lane_wait_ms": 0.5, "latency_ms": 9.0, "bank_generation": 0,
              "kernel_wait_ms": 0.01}
    if chained is not None:
        record["kernel_chained"] = chained
    return record


@pytest.mark.parametrize("chained, want", [
    ([1, 1, 1, 0], 75.0),
    ([0, 0], 0.0),
    ([1], 100.0),
])
def test_the_share_of_windows_served_by_a_chained_kernel(chained, want):
    read = registry.metric_reader("kernel_chained_share")
    windows = [_window(i, c) for i, c in enumerate(chained)]
    assert read(_run(windows)) == pytest.approx(want)


def test_unstamped_records_read_nothing():
    read = registry.metric_reader("kernel_chained_share")
    # the records of a program without the stamp
    assert read(_run([_window(0), _window(1)])) is None
    # one unstamped record among stamped ones
    assert read(_run([_window(0, 1), _window(1)])) is None
    assert read(_run([])) is None
