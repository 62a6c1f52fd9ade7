"""Tests of the benchmark harness (CPU, tier-1)."""
