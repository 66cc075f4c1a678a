"""The on-chip benchmark of MILO: one cell, one run, one process.

``python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip it is started on and prints
one JSON result line.  Every piece is found by name:

- ``bench/configs/<config>.json``: a configuration (shape, source, cuts);
- ``bench/traffic/<mix>.json``: a traffic mix, naming its driver;
- ``bench/drivers/<driver>.py``: set-up, window and check for one kind of cell;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
- ``bench/limits/<cell>.json``: the limit of each number a cell's check
  compares, with the readings it was set from;
- ``bench/references/<name>.py``: the plain references the check compares with.

A later change adds a cell by adding files and an entry in ``BENCHMARK.json``;
no existing file needs an edit.
"""
