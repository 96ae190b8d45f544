"""One reader per metric of BENCHMARK.json: ``read(ctx)`` returns the
metric's value, or None where the run has nothing for it to read (then
the metric is left out of the result).  ``ctx`` is ``harness.Context``."""
