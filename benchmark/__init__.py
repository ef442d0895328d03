"""The benchmark of tpu-rpc: harness, traffic, references and yardsticks.

Entry point: `python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`; `benchmark/README.md` says how cells,
configurations, traffic mixes and per-layer metrics are added as files.
"""
