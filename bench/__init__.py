"""On-chip benchmark of the served render path. ``python3 -m bench.run``
runs one cell; ``BENCHMARK.json`` at the root of the checkout names the
cells and metrics."""
