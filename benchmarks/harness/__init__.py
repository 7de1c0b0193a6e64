"""The benchmark's general code: nothing here names a cell, a
configuration, a traffic mix or a metric (see benchmarks/README.md)."""
