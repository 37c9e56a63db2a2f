"""Chip benchmark of the LLMBridge front door (see ``BENCHMARK.json``).

Run one cell from the root of a checkout::

    python3 bench/run.py --workload qwen2-1.5b.classroom-stream \
        --seed 7 --seconds 40 --trace 0

Everything that belongs to one configuration, traffic mix, cell, per-layer
metric or kernel lives in a file of its own under this directory and is
found by the name ``BENCHMARK.json`` gives it.
"""
