"""The on-chip benchmark: a harness driven by the files beside it (see
``bench/harness.py``)."""
