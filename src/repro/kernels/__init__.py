"""Pallas TPU kernels for the paper's compute hot-spots (DESIGN.md §6):
grouped expert GEMM, paged flash-decode attention, fused top-k router.

Each kernel ships a pure-jnp oracle in ref.py.  Every entry point takes
``interpret``: False (the default) compiles the kernel with Mosaic, which
only a TPU can run; True runs it in the Pallas interpreter, which is how the
CPU tests sweep shapes and dtypes.  The serving backend picks one of the two
from the platform of the device it serves on (``JaxBackend.kernel_mode``).
tests/test_tpu_compile.py compiles each kernel for a v5e at real widths.
"""
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.topk_router import topk_router, topk_router_replicated

__all__ = ["flash_decode", "flash_decode_paged", "moe_gemm", "topk_router",
           "topk_router_replicated"]
