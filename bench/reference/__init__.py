"""Plain float32 references of the configurations the benchmark serves."""
