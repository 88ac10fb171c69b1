"""Code of the benchmark that no later PR needs to touch: the loader, the
traffic generator, the statistics, the trace reduction, the peaks table,
the roofline and the seeded weights."""
