"""The chip benchmark of the function platform (see ``chipbench/run.py``)."""
