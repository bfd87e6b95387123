"""Chip benchmark of the served path: host-clock latency and throughput
from the client's side, per-layer readings from host probes and the device
trace, and an output check against a plain float32 reference.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and per-layer metrics are named in
BENCHMARK.json and found by name under bench/configs, bench/traffic and
bench/metrics.
"""
