"""The engine's ``idle_launch_share`` reader on canned ``window_log``
records: stamped records read their share, and a program whose records
carry no ``launch`` stamp reads nothing."""
import types

import pytest

from bench.common import registry


def _run(windows):
    return types.SimpleNamespace(windows=windows)


def _window(seq, launch=None):
    record = {"seq": seq, "key": "k", "size": 3, "arrival_wait_ms": 6.0,
              "lane_wait_ms": 0.5, "latency_ms": 9.0, "bank_generation": 0}
    if launch is not None:
        record["launch"] = launch
    return record


@pytest.mark.parametrize("launches, want", [
    (["idle", "release", "release", "age"], 75.0),
    (["age", "size", "flush"], 0.0),
    (["release"], 100.0),
])
def test_the_share_of_windows_a_free_lane_launched(launches, want):
    read = registry.metric_reader("idle_launch_share")
    windows = [_window(i, launch) for i, launch in enumerate(launches)]
    assert read(_run(windows)) == pytest.approx(want)


def test_unstamped_records_read_nothing():
    read = registry.metric_reader("idle_launch_share")
    # the records of a program without the stamp
    assert read(_run([_window(0), _window(1)])) is None
    # one unstamped record among stamped ones
    assert read(_run([_window(0, "idle"), _window(1)])) is None
    assert read(_run([])) is None
