"""repro — DoubleR repair layering (arXiv 1704.03696) as a jax system."""
