"""Operator/CI tooling (runnable scripts; importable from the repo
root for chip_smoke.py and the test suite)."""
