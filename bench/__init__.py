"""The benchmark of the reordering-selecting sparse solver (see run.py)."""
