"""CPU tests of the benchmark harness at small sizes."""
