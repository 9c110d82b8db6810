"""Plain float32 references: a decoder, its Adam training steps and its
seeded weights, in straightforward ``jax.numpy``.  Nothing here imports
the program under test."""
