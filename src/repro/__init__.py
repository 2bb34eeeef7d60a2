"""repro: jax_pallas reproduction of LoCo (low-bit communication adaptor)."""
