"""The benchmark harness: finds configurations, traffic mixes, limits,
counts, references and per-layer metric readers by the names that
`BENCHMARK.json` gives, and drives one cell of the benchmark once."""
