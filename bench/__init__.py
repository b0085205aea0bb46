"""On-chip benchmark of the AdaptCL fleet simulator (see ``run.py``)."""
