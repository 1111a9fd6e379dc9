"""device issue: what share of the arrays a pool's fused region programs
return takes over the buffer of an operand the program was given for good:
100 x ``PTDEV_STATS["donated"]`` (slot operands donated: a region is the
last reader of each) over ``PTDEV_STATS["region_outputs"]`` (arrays the
region programs returned, write-backs included). An output the TPU client
must make a new buffer for costs the calling thread ~49 us; a donated
operand's costs nothing. 0 where every operand is a memory read (a k-chain
GEMM). Process-lifetime totals, read after the run, like the readers beside
it. A program without the counters (no region donates) gives nothing to
read."""


def read(run):
    from parsec_tpu.device.native import PTDEV_STATS

    returned = PTDEV_STATS.get("region_outputs")
    if not returned or "donated" not in PTDEV_STATS:
        return None
    return 100.0 * PTDEV_STATS["donated"] / returned
