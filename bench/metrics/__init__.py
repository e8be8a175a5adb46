"""Metric readers: one module per metric of ``BENCHMARK.json``, found by
the metric's name.  Each declares ``LAYER``, ``SOURCE`` and ``MOVES`` as
the benchmark names them, and ``read(run)`` returns the reading, or None
when the run has nothing to read it from."""
