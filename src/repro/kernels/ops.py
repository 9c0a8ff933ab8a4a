"""Public jit'd wrappers around the Pallas kernels.

Each wrapper matches the calling convention used by the model code
(models/attention.py, models/ssm.py, core/ranking hot path) and is validated
against :mod:`repro.kernels.ref` in tests/test_kernels_*.py across shape /
dtype sweeps (interpret mode on CPU; identical call on real TPU with
``interpret=False``).
"""
from __future__ import annotations

from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .gla_chunk import gla_chunk
from .ranking_score import ranking_scores, ranking_victim_order

__all__ = ["flash_attention", "decode_attention", "gla_chunk",
           "gla_chunk_kernel_apply", "ranking_scores",
           "ranking_victim_order"]


def gla_chunk_kernel_apply(q, k, v, log_f, log_i, *, chunk: int = 256,
                           normalize: bool = True, interpret: bool = False):
    """Adapter with the models/ssm.py chunked_gla return convention."""
    return gla_chunk(q, k, v, log_f, log_i, chunk=chunk,
                     normalize=normalize, interpret=interpret)
