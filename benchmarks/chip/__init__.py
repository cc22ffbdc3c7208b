"""Chip benchmark of 0/1 Adam training: one cell of ``BENCHMARK.json`` per run.

``python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the TPU it is started on. Everything that
belongs to one configuration, traffic mix or per-layer metric is a file of
its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json     model and optimizer as run, with its source, and
                            the names of its reference and counts modules
  <reference>.py            the configuration's plain reference
  <counts>.py               the configuration's operations and bytes
  mixes/<traffic>.json      phase of the 0/1 Adam schedule, sequence length,
                            tokens per chip, learning rate
  metrics/<metric>.py       ``read(ctx)`` of one per-layer metric
  limits/<workload>.json    limits of the correctness comparison
  peaks.json                chip peaks keyed by ``device_kind``

A configuration file's top-level ``"reference"`` and ``"counts"`` each name
a module of this directory; there is no default. ``run.load_cell`` imports
both, and the harness and the readers reach the model only through them, so
a new model is new files: its configuration, reference and counts modules,
mix, limits and any readers of its own. Every function takes the
configuration's ``model`` block as ``m``.

A reference module provides:

  init_params(m, key)   {leaf path: float32 array} of the seeded weights,
                        each leaf whole, keyed by the program's parameter
                        paths ("blocks/attn/wq", ...); jittable
  run(m, opt, mix, seed, n_workers, devices=...)
                        the mix's first three steps from the seed, worker i
                        on ``devices[i]``: {"loss": [per step, mean over
                        workers], "grad_norm": {path: norm of the first
                        gradient as the optimizer receives it},
                        "change_norm": {path: norm of worker 0's parameter
                        change}}; it imports nothing of the program
  compare(prog, ref)    {number: {"value", "at"}} of the numbers that decide
                        ``correct``, each held to ``limits/<workload>.json``

``calibrate.py`` also passes ``run`` the keyword ``precision="bfloat16"``
(the control) and ``fault=`` (a planted fault), where a limit is set.

A counts module provides:

  param_count(m)        trainable elements as the program lays them out
  model_flops_per_token(m, seq_len)
                        the operations one trained token requires on this
                        chip's share of the model, forward and backward,
                        PaLM's convention; recomputed operations not counted
  optimizer_bytes(m, n_workers, kind)
                        the HBM bytes one optimizer step of ``kind``
                        (``metrics.step_kind``) must move: leaves that take
                        the data-parallel path at 0/1 Adam's rate, leaves kept
                        out of the exchange at plain Adam's (read g, x, m, v;
                        write x, m, v)

``reference.py`` and ``flops.py`` are these modules for the dense pre-LN
transformer of ``bert-large`` and ``gpt2``. The trace reduction
(``trace.py``, ``scopes.py``) and the chip peaks live here too; none of it
imports the program, apart from its spans, counters and scope names.
"""
