"""On-chip serving benchmark of the quantized engine (see ``bench/run.py``)."""
