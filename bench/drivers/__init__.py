"""Drivers, one module each, found by the workload file's ``driver`` key
(``bench/spec.py``). ``bench/run.py`` calls every driver alike:

- ``build(cell, seed, tmp)``: the system under test, its weights drawn from
  the seed (``tmp`` is the run's scratch directory);
- ``warm(sut)``: every shape the window uses; returns what the check needs
  of set-up;
- ``window(sut, seconds, annotate, tick)``: drive the system for
  ``seconds``; the run's record (``t0``, ``t1``, ``seconds``, ``segments``
  and what the driver keeps);
- ``upto(rec, cut)``: the record as it stood at ``cut``;
- ``e2e(rec)``: the end-to-end numbers, with their ``counts``;
- ``tally(cell, rec, warmed)``: (attempted, failed);
- ``check(cell, seed, rec, warmed, control)``: the numbers compared with
  the reference (with ``control``, also ``control_<number>``);
- ``for_readers(cell, rec)``: what the per-layer readers read beside the
  record of a traced run, ``model_flops`` among it;
- ``rehearse(cell, one, report)``: compile the cell's programs for the
  described device sharding ``one`` (``bench/rehearse.py``).
"""
