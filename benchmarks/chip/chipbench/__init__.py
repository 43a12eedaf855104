"""The chip benchmark's harness: finds cells by name, drives the service
through its public session API, and reduces counters, spans and device
traces to the metrics ``BENCHMARK.json`` names."""
