"""On-chip benchmark of the Synera cloud verifier.

Run one cell with ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout; the cells,
metrics and bounds are in ``BENCHMARK.json``.  Everything that belongs
to one configuration, traffic mix or per-layer metric is a file of its
own under ``chipbench/configs``, ``chipbench/traffic`` and
``chipbench/metrics``, found by the name ``BENCHMARK.json`` gives it.
"""
