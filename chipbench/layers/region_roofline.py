"""kernels: share of the chip's bf16 peak that the fused k-chain program
reaches in its own kernel time: 2 N^3 a traced solve (the graph driver's
``dot_flops``) over the peak of ``peaks.json`` over the device seconds of
``jit_ptg_region_GEMM``. It is ``kernel_roofline``'s reader over this
graph's modules, so that the two read on one scale. f32 at ``HIGHEST`` is
several bf16 passes for each product: the ceiling is about a sixth of that
peak. By the published peaks a 32-tile k-chain at 512 moves 124 FLOP a byte
(``dot_bytes``), under the chip's 240: against the bf16 peak the memory roof
is the nearer one, and ``PERF.md`` gives the share against it beside this."""

from chipbench.layers.kernel_roofline import read  # noqa: F401
