"""Plain references: straightforward ``jax.numpy`` float32, no kernels, no
cache, no batching tricks, independent of the code under test."""
