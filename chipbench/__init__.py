"""On-chip benchmark of H-SGD training (see BENCHMARK.json and PERF.md).

Run one cell as ``python chipbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout on a machine with
a TPU.
"""
