"""Plain float32 references, one per published model type."""
