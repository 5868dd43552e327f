"""Engine: mean wait of an event for its key's model lane, from its window
leaving the batcher to the window's model stage starting (the window's
``lane_wait_ms`` stamp), weighted by window size, over the windows
dispatched inside the measured window (``window_log``)."""


def read(run):
    if not run.windows or any("lane_wait_ms" not in w for w in run.windows):
        return None
    return sum(w["lane_wait_ms"] * w["size"] for w in run.windows) \
        / sum(w["size"] for w in run.windows)
