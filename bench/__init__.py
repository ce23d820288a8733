"""Chip benchmark of the proxy-synthesis system: one cell per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, program kind,
window driver or per-layer metric sits in its own file under
``configs/``, ``traffic/``, ``programs/``, ``windows/`` or ``metrics/``,
found by the name ``BENCHMARK.json`` or the mix gives it.
"""
