"""Model stage: mean time a window's model stage blocks on the device for
its experts' raw scores (the ``muse.models.fetch`` span, stamped into the
window's ``model_fetch_ms``), over the windows dispatched inside the
measured window (``window_log``)."""


def read(run):
    if not run.windows or any("model_fetch_ms" not in w
                              for w in run.windows):
        return None
    return sum(w["model_fetch_ms"] for w in run.windows) / len(run.windows)
