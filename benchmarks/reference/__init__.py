"""The plain reference: float32 ``jax.numpy``, written from the published
architecture descriptions.  Imports nothing of the program."""
