"""Plain float32 reference forwards, one module per model family, named by
a configuration file's ``reference``. Each takes the flax variables of the
program's own model (so both sides compute with the same seeded weights)
and follows the published description in straightforward ``jax.numpy``:
no kernels, no bfloat16, matrix products at the highest precision."""
