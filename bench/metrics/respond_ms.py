"""Engine: mean time of an event from its window's kernel result to the
last of the window's answers delivered (the ``muse.respond`` span's
``respond_ms`` stamp: responses built, the window logged, futures set),
weighted by window size, over the windows dispatched inside the measured
window (``window_log``)."""


def read(run):
    if not run.windows or any("respond_ms" not in w for w in run.windows):
        return None
    return sum(w["respond_ms"] * w["size"] for w in run.windows) \
        / sum(w["size"] for w in run.windows)
