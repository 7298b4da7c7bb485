"""Benchmark of the batched seed sweep on the chip (see ``run.py``)."""
