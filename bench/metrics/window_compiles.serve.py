"""Backend compiles (persistent-cache loads included) while the window
ran, counted through jax.monitoring."""


def read(rec):
    return float(rec["compiles_in_window"])
