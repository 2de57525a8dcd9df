"""Plain references: float32 ``jax.numpy`` written from the published
descriptions. They import nothing of the program."""
