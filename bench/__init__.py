"""The chip benchmark of the MSP brain simulator (see ``run.py``)."""
