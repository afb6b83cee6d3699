"""Plain float32 forwards, one module per architecture family."""
