"""The MaskSearch benchmark harness: data, traffic, load, reference, trace
reduction and the run of one cell (``bench/run.py``)."""
