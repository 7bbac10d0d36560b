"""Pallas TPU kernels (validated in interpret mode on CPU hosts)."""
