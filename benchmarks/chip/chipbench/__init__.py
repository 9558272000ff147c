"""The chip benchmark's library: traffic, weights, the serving window, the
correctness check against a plain reference, and the reduction of a
profiler trace to per-layer metrics. ``benchmarks/chip/run.py`` is the
entry point; ``BENCHMARK.json`` at the checkout's root names the cells.
"""
