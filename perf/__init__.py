"""Perf probes and one-off bench scripts; chip_smoke.py at the repo root is the chip check."""
