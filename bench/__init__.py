"""The chip benchmark of the simulator's jax path (see PERF.md)."""
