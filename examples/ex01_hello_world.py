"""Ex01: one dynamic task (insert_task hello world).

(Reference analogue: examples/Ex01_HelloWorld.c)
"""
from _common import setup

def main():
    setup()
    import numpy as np
    import parsec_tpu as pt
    from parsec_tpu.dsl.dtd import DTDTaskpool, RW

    ctx = pt.init(nb_cores=1)
    tp = DTDTaskpool(ctx, "hello")
    t = tp.tile_new((2, 2), np.float32)

    def hello(x):
        print("hello from a task!")
        return x + 1.0

    tp.insert_task(hello, (t, RW), jit=False)
    tp.wait(); tp.close(); ctx.wait()
    print("ex01 result:", np.asarray(t.data.newest_copy().payload)[0, 0])
    pt.fini()

if __name__ == "__main__":
    main()
