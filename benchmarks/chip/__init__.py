"""The chip benchmark of the co-emulation farm (see ``harness.py``)."""
