"""The chip benchmark of the tridiagonal partition solver (see ``run.py``)."""
