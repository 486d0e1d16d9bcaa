"""Plain float32 references that decide ``correct``."""
