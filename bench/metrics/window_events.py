"""Engine: mean number of events in the windows dispatched inside the
measured window (the engine's ``window_log``)."""


def read(run):
    sizes = [w["size"] for w in run.windows]
    return sum(sizes) / len(sizes) if sizes else None
