"""The chip benchmark: one command serves one cell of ``BENCHMARK.json``.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it (see ``bench/spec.py``).
"""
