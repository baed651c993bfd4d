"""The benchmark's harness: finds cells, configurations, traffic mixes,
statements and metric readers as files, by the names in BENCHMARK.json.
Only ``adapter.py`` imports the program under test."""
