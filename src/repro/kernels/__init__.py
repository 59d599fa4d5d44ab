"""Pallas TPU kernels (interpret=True on CPU) + jnp oracles.

gated_matmul     — zero-tile skipping (the paper's SA gating, TPU-native)
flash_attention  — causal block-skipping online-softmax attention
ssd_scan         — chunked SSD with VMEM-carried state
decode_attention — single-token attention, cache_len block skipping
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
