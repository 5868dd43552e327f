"""Engine: share of the windows dispatched inside the measured window that
left the batcher because their model lane was free, in percent: the
windows whose ``launch`` stamp (``window_log``) is ``idle`` (a free lane at
submit) or ``release`` (a lane freed by a kernel dispatch or a model
stage's end), against those launched by age, size or a forced flush."""


def read(run):
    if not run.windows or any("launch" not in w for w in run.windows):
        return None
    freed = sum(w["launch"] in ("idle", "release") for w in run.windows)
    return 100.0 * freed / len(run.windows)
