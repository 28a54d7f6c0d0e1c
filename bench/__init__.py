"""On-chip benchmark of the pretraining path (see ``run.py``)."""
