"""Reference for ``ptg_gemm_f32_ooc``: C = K A B after K solves over an
accumulating C, at a size whose three matrices do not fit the chip. The
seeded operands, the sample of C tile rows and the comparison computed in
blocks beside a full budget are ``reference/gemm_ooc.py``'s (the operands and
the accumulation are ``dtd_gemm_f32_ooc``'s; only the front end and the lanes
differ), so the twins are held to one comparison. What is this
configuration's own is the value of the bound, set between two readings on
the chip (``configs/ptg_gemm_f32_ooc.json``). Independent of ``parsec_tpu``."""

from chipbench.reference.gemm_ooc import (  # noqa: F401
    max_abs_err, operand_tile, sample_rows, tolerance)
