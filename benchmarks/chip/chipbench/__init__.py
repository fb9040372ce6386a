"""The chip benchmark's harness: finds a cell's parts by name, generates its
traffic, drives the serving engine through a timed window, reduces the
profiler trace and checks the served tokens against a plain reference.
``benchmarks/chip/README.md`` describes the layout."""
