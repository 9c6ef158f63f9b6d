"""On-chip benchmark of the private-serving path (``python bench/run.py``)."""
