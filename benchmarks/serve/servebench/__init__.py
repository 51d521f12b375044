"""The serving benchmark's harness: cells, traffic, client, reduction.

See ``benchmarks/serve/bench.py`` for the entry point."""
