"""Chip benchmark of the served path: ``python bench/run.py --workload ...``.

Everything the benchmark measures with lives here: traffic generation, the
engine feeder, the peak table, the cost model, the trace reduction, one
reader per metric and a plain float32 reference per configuration.  From the
program it takes only the system under test (``repro.serve.ServeEngine``)
and its spans and counters.
"""
