"""On-chip benchmark of the sTiles solver: one cell per run, driven by
BENCHMARK.json and the configuration, traffic and metric files here."""
