"""Plain references: a family's forward pass in straightforward float32
``jax.numpy`` — no kernels, no cache, no batching tricks — that the CPU
tests hold the served path to."""
