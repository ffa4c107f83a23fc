"""On-chip benchmark of the served retrieval path (see ``bench/run.py``)."""
