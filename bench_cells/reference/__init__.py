"""Plain references: straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision, no kernels, no cache, no batching tricks. They import
nothing of the program."""
