"""End-to-end LM training driver: train a ~100M-class model for a few
hundred steps with the full runtime stack (prefetch pipeline, AdamW,
checkpointing, straggler monitor).

The default profile is sized for this CPU container (a reduced-width
qwen3-family model, --profile smoke); --profile 100m selects a genuine
~100M-parameter config (slow on CPU, the TPU-shaped path).

Run:  PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.data import PrefetchPipeline, TokenStream  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.runtime.train import (LoopConfig, TrainLoop,  # noqa: E402
                                 init_train_state, make_train_step)


def profile_100m() -> ModelConfig:
    """~100M params, qwen3-family (qk_norm + GQA)."""
    return ModelConfig(
        arch_id="qwen3-100m", family="dense", n_layers=12, d_model=640,
        n_heads=10, n_kv_heads=2, d_ff=1792, vocab=50304, head_dim=64,
        qk_norm=True, act="swiglu", norm="rmsnorm",
        param_dtype="float32", compute_dtype="float32",
    )


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--profile", choices=["smoke", "100m"], default="smoke")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = (profile_100m() if args.profile == "100m"
           else configs.get_smoke("qwen3-14b"))
    model = build_model(cfg, attn_impl="xla")
    n_params = sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(model.init, jax.random.PRNGKey(0))
        )
    )
    print(f"arch={cfg.arch_id}  params={n_params / 1e6:.1f}M")

    state = init_train_state(model, jax.random.PRNGKey(0))
    opt = AdamWConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps)
    step = jax.jit(make_train_step(model, opt))
    ckpt = CheckpointManager(args.ckpt_dir)

    start_step = 0
    if args.resume and ckpt.latest_step() is not None:
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
        )
        state = ckpt.restore(like)
        start_step = int(state["step"])
        print(f"resumed from checkpoint step {start_step}")

    stream = TokenStream(
        vocab=cfg.vocab, batch=args.batch, seq_len=args.seq_len,
        cfg=cfg, start_step=start_step,
    )
    data = PrefetchPipeline(stream)  # the double-buffered host path

    def on_straggler(step_idx, dt):
        print(f"  [monitor] step {step_idx} straggled ({dt:.2f}s)")

    loop = TrainLoop(
        step, state, data,
        cfg=LoopConfig(total_steps=args.steps, checkpoint_every=50,
                       log_every=10),
        checkpointer=ckpt,
        on_straggler=on_straggler,
    )
    final = loop.run()
    data.close()
    for h in loop.history[:: max(1, len(loop.history) // 10)]:
        print(f"step {h['step']:>5}  loss {h['loss']:.4f}  {h['dt']*1e3:.0f}ms")
    print(f"final step {int(final['step'])}, "
          f"loss {loop.history[-1]['loss']:.4f} "
          f"(from {loop.history[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
