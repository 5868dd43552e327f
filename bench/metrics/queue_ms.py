"""Engine: mean wait before the model stage, over the window's answered
arrivals: latency from the due time minus the response's ``latency_ms``,
which runs from the model stage's start to the answer."""
import numpy as np


def read(run):
    return float(np.mean(run.queue_ms)) if len(run.queue_ms) else None
