"""The on-chip benchmark: ``bench/run.py`` runs one cell once."""
