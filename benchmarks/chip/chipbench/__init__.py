"""Chip benchmark harness for the RECALL serving path (see ``run.py``)."""
