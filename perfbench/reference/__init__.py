"""Plain references: the estimators' published objectives in straightforward
jax.numpy, float32 at ``highest``, in blocks of rows. They import nothing of
the program and take nothing it made: only the benchmark's own X and y."""
