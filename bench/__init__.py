"""On-chip benchmark of the served estimator (see ``bench/run.py``)."""
