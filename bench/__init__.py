"""The serving benchmark: one cell per run, driven by the files named in
BENCHMARK.json (configs, traffic mixes, per-layer metric readers).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
