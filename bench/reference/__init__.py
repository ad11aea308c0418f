"""Plain float32 references of the configured architectures."""
