"""1-bit Adam reproduction (jax_pallas)."""
