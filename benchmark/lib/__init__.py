"""The benchmark's general machinery. Nothing in this package names a
cell, a configuration, a traffic mix or a metric: those are files found
by the names in BENCHMARK.json."""
