"""perfbench — the benchmark of BENCHMARK.json: harness, yardstick, data.

Everything a cell needs is found by the names in BENCHMARK.json; see
README.md.  A regular package (not a namespace one) so that
``tests/perfbench`` on a pytest ``sys.path`` can never shadow it.
"""
