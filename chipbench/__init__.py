"""Chip benchmark of repro.pum (see chipbench/harness.py)."""
