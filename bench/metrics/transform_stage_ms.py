"""Mean duration of the benchmark's ``bench.apply_transforms`` span (around
``apply_transforms``) over the traced window, in milliseconds."""


def read(run):
    lo, hi = run.trace_window or (0, 0)
    spans = [d for s, d in (run.spans or {}).get("bench.apply_transforms", ())
             if lo <= s <= hi]
    return sum(spans) / len(spans) * 1e-6 if spans else None
