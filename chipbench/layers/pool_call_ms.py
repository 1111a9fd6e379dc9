"""device issue: the ``ptdev.call`` spans of a pool, milliseconds a pool,
the median over the accounts the program filed (``call_ns``;
``chipbench/layers/pool_account.py``). One span a device program, around
the jitted call alone: jax's argument handling, the TPU client's enqueue,
the output buffers. In ``ptg_gemm.ts512`` it also holds the chip's time:
once the device's queue is full the client blocks the caller until a
program ends, so most of a solve's kernel seconds stand here and the
host's own price of a call does not show; read ``pool_own_ms`` and
``host_cpu_per_task`` beside it."""

from chipbench.layers.pool_account import median_ms


def read(run):
    return median_ms("call_ns")
