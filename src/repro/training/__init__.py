"""Training substrate: optimizer, steps, data, sharding specs (checkpoints:
repro.resilience.checkpoint)."""
