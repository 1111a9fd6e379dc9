"""Ex15: multi-host scale-out — one GLOBAL mesh spanning OS processes.

Run it directly::

    python examples/ex15_multihost.py

With no controller env set, the script plays mpirun: it relaunches itself
as TWO controller processes (4 virtual CPU devices each) joined into ONE
jax job by ``jax.distributed.initialize``. Inside a controller,
``jax.devices()`` lists all EIGHT devices — four local, four owned by the
peer process — and a single ``Mesh`` spans them. The flagship LM train
step then runs over that global (dp, tp) mesh unchanged: XLA's
collectives cross the process boundary (ICI/DCN on a real pod; Gloo on
this CPU rehearsal), and both controllers observe bit-identical losses,
because there is only ONE program. This is the reference's
mpirun-over-MPI/NCCL scale-out with the entire data plane handed to the
compiler (SURVEY §2.3/§2.8).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from _common import setup  # noqa: E402


def controller():
    setup()
    import jax
    from parsec_tpu.parallel.multihost import (fetch_replicated,
                                               global_mesh, init_multihost)
    pid = init_multihost()

    import numpy as np
    from parsec_tpu.parallel.model import (ModelConfig, init_lm_params,
                                           make_lm_train_step)

    mesh = global_mesh(("dp", "tp"), (2, 4))
    local = len(jax.local_devices())
    print(f"controller {pid}: {local} local of {len(jax.devices())} global "
          f"devices; mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}",
          flush=True)

    cfg = ModelConfig(vocab_size=64, d_model=32, d_ff=64, n_heads=4,
                      n_layers=2, max_seq=16)
    params = init_lm_params(0, cfg)          # identical on every controller
    step, place_p, place_t = make_lm_train_step(mesh, params=params, lr=0.1)
    params = place_p(params)
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 64, size=(8, 8)).astype(np.int32)
    tokens, targets = place_t(toks[:, :-1]), place_t(toks[:, 1:])
    for i in range(3):
        params, loss = step(params, tokens, targets)
        print(f"controller {pid}: step {i} loss "
              f"{float(fetch_replicated(loss)):.4f}", flush=True)


def main():
    from parsec_tpu.parallel.multihost import ENV_NPROC, run_multicontroller
    if os.environ.get(ENV_NPROC):
        controller()
        return
    outs = run_multicontroller(2, os.path.abspath(__file__),
                               devices_per_proc=4)
    for o in outs:
        sys.stdout.write(o)
    # both controllers printed the same losses: one global program
    l0 = [ln for ln in outs[0].splitlines() if "loss" in ln]
    l1 = [ln for ln in outs[1].splitlines() if "loss" in ln]
    assert [s.split("loss")[1] for s in l0] == \
        [s.split("loss")[1] for s in l1]
    print("multi-controller OK: identical losses on both controllers")


if __name__ == "__main__":
    main()
