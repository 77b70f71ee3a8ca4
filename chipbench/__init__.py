"""On-chip benchmark of the FUSEE device store (see ``run.py``)."""
