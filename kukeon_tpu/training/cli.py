"""Training entrypoint: data pipeline + sharded train step + checkpoints.

    python -m kukeon_tpu.training.cli \
        --dataset /data/tokens.bin --model llama3-8b \
        --tensor 4 --fsdp 2 --steps 10000 --ckpt-dir /ckpts --save-every 500

Composes the framework's training pieces end to end: memmapped token
batches (deterministic, resume-aligned), the dense / MoE / pipeline train
steps over the canonical mesh axes, and orbax checkpoints (auto-resume
from the newest step in --ckpt-dir). The compute path is jit-compiled
once; the loop is pure orchestration.
"""

from __future__ import annotations

import argparse
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="kukeon-train")
    ap.add_argument("--dataset", required=True, help="token .bin file")
    ap.add_argument("--model", default="tiny",
                    choices=["tiny", "llama3-1b", "llama3-8b",
                             "mixtral-tiny", "mixtral-8x7b"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup-steps", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=500)
    ap.add_argument("--log-every", type=int, default=10)
    for axis in ("data", "fsdp", "tensor", "seq", "expert", "pipe"):
        ap.add_argument(f"--{axis}", type=int, default=1)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    import jax

    from kukeon_tpu.models import llama, moe
    from kukeon_tpu.parallel import make_mesh
    from kukeon_tpu.training import (
        TokenDataset,
        batches,
        create_moe_train_state,
        create_train_state,
        latest_step,
        restore_checkpoint,
        save_checkpoint,
    )
    from kukeon_tpu.training.train_step import (
        make_moe_train_step,
        make_optimizer,
        make_train_step,
    )

    is_moe = args.model.startswith("mixtral")
    cfgs = {
        "tiny": llama.llama_tiny, "llama3-1b": llama.llama3_1b,
        "llama3-8b": llama.llama3_8b,
        "mixtral-tiny": moe.moe_tiny, "mixtral-8x7b": moe.mixtral_8x7b,
    }
    cfg = cfgs[args.model]()

    import math

    n = len(jax.devices())
    sizes = {a: getattr(args, a) for a in
             ("data", "fsdp", "tensor", "seq", "expert", "pipe")}
    specified = 1
    for v in sizes.values():
        specified *= v
    if specified == 1 and n > 1:
        # Default: pure data parallelism over as many devices as the batch
        # divides into (a 4-sample batch on an 8-device host uses 4).
        sizes["data"] = math.gcd(n, args.batch)
        specified = sizes["data"]
    mesh = make_mesh(**sizes, devices=jax.devices()[:specified])
    print(f"train: model={args.model} mesh={dict(mesh.shape)} "
          f"batch={args.batch} seq={args.seq_len}", flush=True)

    ds = TokenDataset(args.dataset)
    optimizer = make_optimizer(
        learning_rate=args.lr, warmup_steps=args.warmup_steps,
        total_steps=max(args.steps, args.warmup_steps + 1),
    )

    with jax.set_mesh(mesh):
        if is_moe:
            if sizes["pipe"] > 1:
                print("error: pipeline parallelism is llama-only for now",
                      file=sys.stderr)
                return 2
            state, optimizer = create_moe_train_state(
                cfg, mesh, jax.random.key(args.seed), optimizer)
            step_fn, batch_sharding = make_moe_train_step(cfg, mesh, optimizer)
        elif sizes["pipe"] > 1:
            from kukeon_tpu.parallel.pipeline import (
                make_pp_train_step,
                pp_specs_for_params,
            )

            state, optimizer = create_train_state(
                cfg, mesh, jax.random.key(args.seed), optimizer,
                init_fn=lambda k: llama.init_params(k, cfg),
                specs=pp_specs_for_params(
                    jax.eval_shape(lambda k: llama.init_params(k, cfg),
                                   jax.random.key(args.seed))
                ),
            )
            step_fn = make_pp_train_step(cfg, mesh, optimizer)
            batch_sharding = None
        else:
            state, optimizer = create_train_state(
                cfg, mesh, jax.random.key(args.seed), optimizer)
            step_fn, batch_sharding = make_train_step(cfg, mesh, optimizer)

        start = 0
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            from kukeon_tpu.training import abstract_like

            # Free the throwaway init BEFORE the restore reads the
            # checkpoint copy in — otherwise peak HBM is 2x the model
            # state and an 8B resume OOMs where from-scratch trains fine.
            template = abstract_like(state)
            state = None
            state = restore_checkpoint(args.ckpt_dir, template)
            start = int(state.step)
            print(f"train: resumed from step {start}", flush=True)

        t0 = time.monotonic()
        last_logged = start
        for step, tok, tgt, mask in batches(
            ds, args.batch, args.seq_len, start_step=start,
            num_steps=args.steps - start, seed=args.seed,
            sharding=batch_sharding,
        ):
            state, out = step_fn(state, tok, tgt, mask)
            loss = out["loss"] if isinstance(out, dict) else out
            if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
                dt = time.monotonic() - t0
                window = step + 1 - last_logged   # may be < log_every at the tail
                tput = args.batch * args.seq_len * window / max(dt, 1e-9)
                extra = ""
                if isinstance(out, dict):
                    extra = f" lb={float(out['load_balance']):.3f}"
                print(f"step {step + 1} loss {float(loss):.4f}{extra} "
                      f"({tput:.0f} tok/s)", flush=True)
                t0 = time.monotonic()
                last_logged = step + 1
            if (args.ckpt_dir and args.save_every
                    and (step + 1) % args.save_every == 0):
                save_checkpoint(args.ckpt_dir, state)
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, state)
            print(f"train: checkpoint at step {int(state.step)} -> "
                  f"{args.ckpt_dir}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
