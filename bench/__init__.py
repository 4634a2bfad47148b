"""On-chip benchmark of the TD-VMM serving engine (see BENCHMARK.json).

Run one cell with ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.  Everything a cell is made of is found by
name: ``configs/<config>.json``, ``traffic/<mix>.json``, one reducer
``metrics/<metric>.py`` per per-layer metric, and the package
``archs/<name>/`` of the configuration's architecture class.
"""
