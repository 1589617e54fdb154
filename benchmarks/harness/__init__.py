"""The yardstick: traffic generation, statistics, counts, peaks, the trace
reduction, seeded weights and the drivers.  Later PRs add files; they do not
edit these."""
