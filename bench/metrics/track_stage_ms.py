"""Mean duration of the benchmark's ``bench.track`` span (around
``track``) over the traced window, in milliseconds."""


def read(run):
    lo, hi = run.trace_window or (0, 0)
    spans = [d for s, d in (run.spans or {}).get("bench.track", ())
             if lo <= s <= hi]
    return sum(spans) / len(spans) * 1e-6 if spans else None
