"""Chip benchmark of the serverless-scheduling simulator (see run.py)."""
