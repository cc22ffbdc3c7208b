"""Chip benchmark of 0/1 Adam training: one cell of ``BENCHMARK.json`` per run.

``python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the TPU it is started on. Everything that
belongs to one configuration, traffic mix or per-layer metric is a file of
its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json     model and optimizer as run, with its source
  mixes/<traffic>.json      phase of the 0/1 Adam schedule, sequence length,
                            tokens per chip, learning rate
  metrics/<metric>.py       ``read(ctx)`` of one per-layer metric
  limits/<workload>.json    limits of the correctness comparison
  peaks.json                chip peaks keyed by ``device_kind``

The reference that decides ``correct`` (``reference.py``), the trace
reduction (``trace.py``) and the counts of operations and bytes
(``flops.py``) live here too and import nothing of the program.
"""
