"""Plain float32 reference of the served models, independent of ``repro``."""
