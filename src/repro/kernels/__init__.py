"""Pallas TPU kernels.

Each subpackage: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle). Validated in interpret=True on CPU;
TPU is the compile target.

Two families run in the checkpoint path on a TPU: ``fingerprint`` (the
device-delta tracker's per-block digests) and ``quantize`` (on-device int8
moments for urgent saves, and the restore's dequantize). ``flash_attention``
runs the model's sequence attention on a TPU (``models.transformer``
dispatches to it by backend and shape). ``tests/test_tpu_compile.py``
compiles all three for a described v5e. The decode attention and scan
kernels (``flash_decode``, ``ssm_scan``, ``rglru_scan``) mirror the model's
XLA paths (single-token attention, associative scans) and are tested
against them, but ``models/`` never calls them.
"""

from .decode_attention import decode_attention_ref, flash_decode
from .flash_attention import attention_ref, flash_attention
from .quantize import quantize_int8, quantize_int8_ref
from .rglru_scan import lru_scan, rglru_scan, rglru_scan_ref
from .ssm_scan import selective_scan, ssm_scan, ssm_scan_ref

__all__ = [
    "attention_ref", "decode_attention_ref", "flash_attention", "flash_decode",
    "lru_scan", "quantize_int8", "quantize_int8_ref", "rglru_scan",
    "rglru_scan_ref", "selective_scan", "ssm_scan", "ssm_scan_ref",
]
