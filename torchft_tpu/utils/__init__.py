"""Shared utilities: profiling spans, timing helpers."""

from torchft_tpu.utils.profiling import trace_span

__all__ = ["trace_span"]
