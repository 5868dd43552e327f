"""Engine: mean wait of an event in the batcher, from its arrival at
``AsyncDispatchEngine.submit`` to its window leaving the batcher, over the
windows dispatched inside the measured window: the sum of the windows'
``arrival_wait_ms`` stamps over the sum of their sizes (``window_log``)."""


def read(run):
    if not run.windows or any("arrival_wait_ms" not in w
                              for w in run.windows):
        return None
    return sum(w["arrival_wait_ms"] for w in run.windows) \
        / sum(w["size"] for w in run.windows)
