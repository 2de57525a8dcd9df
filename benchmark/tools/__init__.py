"""Helpers for the person measuring: none of them is part of a run."""
