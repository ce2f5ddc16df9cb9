"""Chip benchmark of n-TangentProp: PINN training and derivative tables.
Run one cell with ``python3 bench/run.py``."""
