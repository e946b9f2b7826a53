"""Chip benchmark of the adaptive priority queue; run `python3 bench/run.py --help`."""
