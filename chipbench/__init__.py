"""Chip benchmark of the job half: data-driven cells run on a TPU."""
