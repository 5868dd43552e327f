"""Transform stage: share of the windows dispatched inside the measured
window whose banked kernel the model stage queued behind their forward and
the transform stage served, in percent: the windows whose
``kernel_chained`` stamp (``window_log``) is 1, against those whose
transform stage dispatched the kernel itself (0)."""


def read(run):
    if not run.windows or any("kernel_chained" not in w
                              for w in run.windows):
        return None
    return 100.0 * sum(w["kernel_chained"] for w in run.windows) \
        / len(run.windows)
