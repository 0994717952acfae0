"""The on-chip benchmark's harness: one cell of ``BENCHMARK.json``, run once.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own beside this package, found by name:

* ``configs/<config>.json``: the model's sizes as served; its ``reference``
  names ``references/<reference>.py``, the plain float32 forward pass, the
  weight generator and the operation counts;
* ``traffic/<traffic>.json``: the parameters the one request generator
  (``traffic.py``) reads;
* ``metrics/<metric>.py``: one reader per metric, ``read(ctx)``;
* ``limits/<workload>.json``: the limits of the numbers that decide
  ``correct``, with the readings they were set from;
* ``peaks.json``: the chip's published peaks, keyed by ``device_kind``.
"""
