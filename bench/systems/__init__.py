"""The systems under test, one file per model family."""
