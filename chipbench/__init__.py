"""chipbench: the benchmark that runs ray_tpu's user paths on the chip.

One command per cell and run (``python -m chipbench.run``); see
``BENCHMARK.json`` for the cells and ``PERF.md`` for why each exists.
"""
