"""Plain references the benchmark checks the program's outputs against."""
