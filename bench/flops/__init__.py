"""FLOP counts from shapes, one file per model family."""
