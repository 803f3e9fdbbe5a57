"""The chip benchmark of the SageSched serving stack (see ``run.py``)."""
