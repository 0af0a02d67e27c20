"""The yardstick: traffic, references, counts, trace reduction and the
harness that turns one cell of ``BENCHMARK.json`` into one result line.
From the program it takes only the system under test and its counters."""
