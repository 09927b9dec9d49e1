"""Harness of the LARK engine benchmark (see ../run.py)."""
