"""Ex00: runtime lifecycle — init, start, wait, fini.

(Reference analogue: examples/Ex00_StartStop.c)
"""
from _common import setup

def main():
    setup()
    import parsec_tpu as pt
    ctx = pt.init(nb_cores=1)
    ctx.start()
    ctx.wait()           # no taskpools: returns immediately
    pt.fini()
    print("ex00: context lifecycle OK")

if __name__ == "__main__":
    main()
