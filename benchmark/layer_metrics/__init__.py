"""One file per per-layer metric, found by the metric's name in
BENCHMARK.json. Each exposes ``read(obs)`` over the run's observations
(:class:`benchmark.harness.Observations`: the program's step events, the
engine's stats, the reduced device trace, the benchmark's own spans) and
returns the value, or None when there is nothing to read — the harness
then leaves the metric out of the line."""
