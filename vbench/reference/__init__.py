"""Plain references: one module a model family, found by the ``family`` key
of a configuration's file. Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision, no kernels, no cache, no batching; nothing
here imports the program."""
