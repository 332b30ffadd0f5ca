"""The benchmark the driver runs: see BENCHMARK.json and PERF.md.

Everything the yardstick needs lives here: traffic generation, the plain
references, the reduction from spans and device traces to metrics, the table
of peaks. From the program it takes only the system under test and its
spans, counters and kernel names.
"""
