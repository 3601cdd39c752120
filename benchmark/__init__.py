"""The benchmark: the yardstick later PRs are held to and may not change.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``; ``README.md`` says how
cells, configurations, traffic mixes and layer metrics are added as files.
"""
