"""The chip benchmark of poisson_tpu: ``python perf/run.py --workload ...``.

Everything that belongs to one configuration, traffic mix, driver or metric
sits in a file of its own and is found by the name ``BENCHMARK.json`` gives
it; see ``perf/run.py``.
"""
