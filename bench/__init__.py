"""The on-chip benchmark of the encrypted query engine.

`BENCHMARK.json` at the repository root names the cells; this package
holds the harness (`run.py`), its yardstick (data generator, plain
reference, trace reduction, peaks, roofline byte counts) and the files
it finds by name: `configs/<config>.json`, `traffic/<mix>.json` and
`metrics/<metric>.py`.
"""
