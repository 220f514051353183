"""On-chip benchmark of the X-STCC epoch engine (see ``run.py``)."""
