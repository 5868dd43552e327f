"""Mean duration of the benchmark's ``bench.run_models`` span (around
``run_models``) over the traced window, in milliseconds."""


def read(run):
    lo, hi = run.trace_window or (0, 0)
    spans = [d for s, d in (run.spans or {}).get("bench.run_models", ())
             if lo <= s <= hi]
    return sum(spans) / len(spans) * 1e-6 if spans else None
