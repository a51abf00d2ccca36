"""Host oracle backend of the frozen reference (python_backend only)."""
