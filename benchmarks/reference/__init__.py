"""Plain references: straightforward jax.numpy, float32, no cache, no kernels. They import nothing of the program."""
