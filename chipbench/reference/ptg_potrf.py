"""Reference for ``ptg_potrf_f32``: the seeded SPD matrix, the sample of
lower tiles and the residual ||(L L^T - A)[S]||_F / ||A[S]||_F are
``reference/potrf.py``'s (the DAG is ``dtd_potrf_f32``'s; only the front end
and the lanes differ), so the twins are held to one comparison. What is this
configuration's own is the value of the bound, set between two readings on
the chip (``configs/ptg_potrf_f32.json``). Independent of ``parsec_tpu``."""

from chipbench.reference.potrf import (  # noqa: F401
    residual, sample_tiles, spd_tile)
