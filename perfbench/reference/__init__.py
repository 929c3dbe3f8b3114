"""Plain references that decide ``correct``; they import nothing of the
program under test (a model family's ``program_config`` alone builds the
program's configuration, and the reference never calls it)."""
