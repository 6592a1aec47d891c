"""Chip benchmark of the Hadar scheduler: replayed Philly deployments,
timed per consult, checked against a plain reference (see run.py)."""
