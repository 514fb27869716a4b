"""The on-chip benchmark of the federation round (see ``harness``)."""
