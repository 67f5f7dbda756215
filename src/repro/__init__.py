"""repro: multicast-crossbar paper reproduction on jax/Pallas."""
