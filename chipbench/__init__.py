"""Chip benchmark of the signSGD majority-vote trainer (see run.py)."""
