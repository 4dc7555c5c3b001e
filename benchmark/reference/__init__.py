"""Plain references the benchmark decides `correct` against."""
