"""Whole-forward FLOP/s utilisation of a full-graph cell (traced run)."""
from metrics._mfu import read  # noqa: F401
