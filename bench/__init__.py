"""On-chip serving benchmark of the OmniSense detector pod (see run.py)."""
