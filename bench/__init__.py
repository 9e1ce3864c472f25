"""Chip benchmark of the batched priority queue.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once, on the TPU
chips of the machine it is started on, and prints one JSON result line.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own that the harness finds by name:

* ``bench/configs/<config>.json`` -- the deployment: engine spec, chips,
  resident depth, source, cuts and the guarantee it is held to;
* ``bench/traffic/<mix>.json`` -- parameters that the one generator
  (``bench/generate.py``) reads;
* ``bench/metrics/<metric>.py`` -- a ``read(obs)`` that reduces one
  run's observation to one number, or ``None`` where it finds nothing.

The benchmark takes only the system under test from ``src/repro``; the
traffic, the exact reference (``bench/reference.py``), the trace
reduction (``bench/trace.py``) and the comparison that decides
``correct`` live here.
"""
