"""Pallas TPU kernels for the assigned architectures' compute hot spots.

flash_attention — causal GQA attention w/ online softmax + sliding window
ssd_scan        — Mamba2 SSD chunked scan with carried VMEM state
fused_update    — FL update hot loop over FlatView flat buffers (client
                  step tail, weighted-delta aggregation, server moments)

``ops`` holds the jit'd wrappers; ``ref`` the pure-jnp oracles.  On a
TPU the kernels lower to Mosaic; on any other backend they run in the
Pallas interpreter, which is how the CPU tests check them against the
oracles.  ``parity`` runs every fused update kernel against its oracle
at any width (``chip_smoke.py`` does so with Mosaic at qwen1.5-0.5b's
flat length; ``tests/test_chip_compile.py`` compiles them for a
described v5e chip).
"""
from repro.kernels import fused_update, ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
