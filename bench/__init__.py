"""On-chip benchmark of the estimator system (see ``BENCHMARK.json``)."""
