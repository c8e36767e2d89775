"""Plain float32 references the tests and the benchmark compare the system with; they import nothing of it."""
