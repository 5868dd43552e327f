"""Transform stage: mean time a window's transform stage blocks on the
device for the banked kernel's scores (the ``muse.transforms.fetch`` span,
stamped into the window's ``kernel_wait_ms``), over the windows dispatched
inside the measured window (``window_log``)."""


def read(run):
    if not run.windows or any("kernel_wait_ms" not in w
                              for w in run.windows):
        return None
    return sum(w["kernel_wait_ms"] for w in run.windows) / len(run.windows)
