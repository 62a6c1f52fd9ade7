"""The benchmark's own library: traffic, load, reductions, reference."""
