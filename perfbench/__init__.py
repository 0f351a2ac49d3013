"""Chip benchmark of the served int4 tp-aware decode path.

One run of one cell:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout root names the cells, configurations and
metrics.  Everything that belongs to one of them is a file of its own,
found by name:

* ``configs/<config>.json``  model sizes as run, with source and cuts;
* ``traffic/<mix>.json``     parameters of the one traffic generator;
* ``cells/<cell>.json``      batch slots, cache length and the limits of
  the correctness comparison;
* ``metrics/<metric>.py``    a ``read(run)`` that reduces one run's
  records or trace to the metric, or returns None where it finds nothing;
* ``peaks.json``             published peaks keyed by ``device_kind``.

The yardstick (traffic, arithmetic, trace reduction, weights, the plain
reference) lives here; from the program the benchmark takes only the
serving engine, its counters and the names its kernels carry in a trace.
"""
