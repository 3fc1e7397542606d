"""On-chip serving benchmark: one cell, one run, one result line.

Entry point: ``python3 benchmarks/chip/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Everything that belongs to one model
configuration, traffic mix, placement or metric is a file of its own under
this directory, found by the name ``BENCHMARK.json`` gives it.
"""
