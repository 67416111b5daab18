"""Whole-forward FLOP/s utilisation of the screening cell (traced run)."""
from metrics._mfu import read  # noqa: F401
