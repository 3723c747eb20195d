"""Plain references the benchmark checks the served path against."""
