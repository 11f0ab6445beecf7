"""Plain float32 references, one module per model family."""
