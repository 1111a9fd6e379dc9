"""Ex13: the flagship model family — LM training end to end.

Runs on an 8-device virtual mesh (works anywhere):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/ex13_lm_training.py

1. A GPT-class LM (`parallel/model.py`) trains under a (dp, tp) GSPMD
   mesh with AdamW (optax): batch over dp, Megatron-split blocks and a
   vocab-parallel tied embedding/head over tp, optimizer moments sharded
   like their parameters.
2. The full training state checkpoints through orbax
   (`utils/model_ckpt`) and training RESUMES bit-exact from the restore.
3. The trained model reproduces the memorized token stream through
   KV-cached greedy generation (`lm_generate`: prefill + lax.scan decode
   in one compiled program), and the Pallas flash-attention core's
   forward logits are checked against the dense core's.
"""
import os
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from _common import setup  # noqa: E402


def main():
    setup()
    import numpy as np
    import optax

    from parsec_tpu.parallel.model import (ModelConfig, init_lm_params,
                                           lm_apply, lm_generate,
                                           make_lm_opt_train_step)
    from parsec_tpu.parallel.spmd import make_mesh
    from parsec_tpu.parallel.transformer import flash_attention_core
    from parsec_tpu.utils.model_ckpt import (restore_train_state,
                                             save_train_state)

    mesh = make_mesh(8, axis_names=("dp", "tp"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    cfg = ModelConfig(vocab_size=16, d_model=64, d_ff=128, n_heads=4,
                      n_layers=2, max_seq=32)
    params = init_lm_params(0, cfg)

    # the corpus: a periodic token stream the model must memorize
    pattern = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    seq = np.tile(pattern, 8)[:33]
    toks = np.broadcast_to(seq, (4, 33)).copy()       # dp batch of 4
    x, y = toks[:, :-1], toks[:, 1:]

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-2))
    step, opt_state, place_p, place_t = make_lm_opt_train_step(
        mesh, tx, params)
    sp = place_p(params)
    xt, yt = place_t(x), place_t(y)

    for i in range(60):
        sp, opt_state, loss = step(sp, opt_state, xt, yt)
        if i % 20 == 0:
            print(f"  step {i:3d}  loss {float(loss):.4f}")

    # checkpoint mid-training, then resume from the restore
    with tempfile.TemporaryDirectory() as d:
        path = save_train_state(os.path.join(d, "ckpt"), sp, opt_state,
                                step=60)
        rp, ro, rstep = restore_train_state(path, like=(sp, opt_state))
        print(f"checkpoint saved+restored at step {rstep}")
        for i in range(30):
            rp, ro, loss = step(rp, ro, xt, yt)
    print(f"final loss after resume: {float(loss):.5f}")

    # KV-cached greedy generation: prefill + lax.scan decode, ONE compiled
    # program (`lm_generate`); plus a flash-attention-core forward check
    out = np.asarray(lm_generate(rp, seq[None, :8].astype(np.int32), 16))
    decoded = [int(v) for v in out[0, 8:]]
    expected = [int(v) for v in np.tile(pattern, 3)[:16]]
    print(f"greedy decode: {decoded}")
    assert decoded == expected, f"decode mismatch: {decoded} != {expected}"
    flash_logits = np.asarray(lm_apply(rp, out,
                                       attention=flash_attention_core))
    dense_logits = np.asarray(lm_apply(rp, out))
    assert np.abs(flash_logits - dense_logits).max() < 2e-3
    print("ex13 OK: LM trained (dp x tp + AdamW), checkpoint/resume, "
          "KV-cached generation reproduces the stream, flash core matches")


if __name__ == "__main__":
    main()
