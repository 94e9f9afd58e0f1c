"""train — flagship sharded-training demo with checkpoint/resume + tracing.

The reference's examples exercise its transport (helloworld, bounce); this
one exercises everything the tpu rebuild adds on top: a decoder-only
Transformer LM trained with one ``jit``-compiled step over a dp/sp/tp
device mesh (GSPMD inserts the gradient psum and tensor-parallel
reductions), flash/ring attention kernels, checkpoint/resume, and the
tracing subsystem.

Run (any machine — virtual CPU mesh)::

    python examples/train.py --devices 8 --steps 20
    python examples/train.py --devices 8 --steps 20 --resume  # continue
    python examples/train.py --attention ring                 # sp ring

On a real TPU slice drop ``--devices`` (uses every chip).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual CPU device count (default: real devices)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=33)
    ap.add_argument("--attention", default="dense",
                    choices=["dense", "flash", "blockwise", "ring",
                             "ring_flash", "zigzag", "zigzag_flash",
                             "ulysses", "ulysses_flash"])
    ap.add_argument("--remat", action="store_true",
                    help="rematerialise each block in the backward, "
                         "holding only the attention kernels' output and "
                         "log-sum-exp and the FFN's first matmul output "
                         "(train longer sequences in the same HBM)")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatches per optimizer step")
    ap.add_argument("--corpus", default=None,
                    help="raw binary uint16 token file to train on "
                         "(memory-mapped; native gather kernel); token "
                         "ids must be < 256, this example's vocab. "
                         "default: synthetic stream")
    ap.add_argument("--zero1", action="store_true",
                    help="shard optimizer state over dp (ZeRO-1)")
    ap.add_argument("--fsdp", action="store_true",
                    help="fully shard the parameters over dp "
                         "(ZeRO-3/FSDP; subsumes --zero1)")
    ap.add_argument("--lora", type=int, default=0, metavar="RANK",
                    help="freeze the base model and train rank-RANK "
                         "LoRA adapters instead (adapter-only state)")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "sgd"])
    ap.add_argument("--warmup-steps", type=int, default=0,
                    help="linear LR warmup; with --steps it becomes "
                         "warmup + cosine decay")
    ap.add_argument("--checkpoint-dir", default="/tmp/mpi_tpu_train_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--trace", default=None,
                    help="write a chrome://tracing JSON here at exit")
    ap.add_argument("--sample", type=int, default=0,
                    help="after training, greedily generate N tokens")
    args, _ = ap.parse_known_args()

    if args.devices:
        from mpi_tpu.utils.platform import force_platform

        force_platform("cpu", args.devices)
    import jax

    from mpi_tpu.data import ShardedLoader, SyntheticLM
    from mpi_tpu.models import TransformerConfig, make_mesh_nd, make_train_step
    from mpi_tpu.utils import (AsyncCheckpointer, latest_step,
                               restore_checkpoint, trace)

    if args.trace:
        trace.enable()

    n = len(jax.devices())
    mesh = make_mesh_nd(n)
    cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_seq=64,
                            attention_impl=args.attention,
                            remat=args.remat)
    print(f"mesh={dict(mesh.shape)} attention={args.attention} "
          f"remat={args.remat} grad_accum={args.grad_accum}")

    # Resolve the resume point BEFORE building the step: the LR schedule
    # horizon is the absolute final step (start + steps), so a resumed
    # run continues the same warmup/cosine curve instead of restarting
    # its decay from the restored optimizer count.
    start = 0
    if args.resume:
        last = latest_step(args.checkpoint_dir)
        if last is not None:
            start = last
    lora_base = None
    if args.lora:
        # Adapter-only fine-tuning: a frozen (sharded) base + LoRA
        # deltas trained in its place. The base here is fresh-init for
        # demo purposes; real use restores it from a checkpoint.
        unsupported = [n for n, v in (("--grad-accum", args.grad_accum > 1),
                                      ("--warmup-steps", args.warmup_steps),
                                      ("--zero1", args.zero1),
                                      ("--fsdp", args.fsdp),
                                      ("--resume", args.resume)) if v]
        if unsupported:
            raise SystemExit(
                f"--lora does not support {', '.join(unsupported)} in "
                f"this demo (adapter state has its own shape)")
        from mpi_tpu.models import init_sharded_params, make_lora_train_step

        lora_base = init_sharded_params(jax.random.PRNGKey(0), cfg, mesh)
        init_state, step = make_lora_train_step(
            cfg, lora_base, rank=args.lora, mesh=mesh, learning_rate=1e-2,
            optimizer=args.optimizer)
    else:
        init_state, step = make_train_step(
            cfg, mesh=mesh, learning_rate=1e-2, grad_accum=args.grad_accum,
            optimizer=args.optimizer, warmup_steps=args.warmup_steps,
            total_steps=start + args.steps if args.warmup_steps else None,
            zero1=args.zero1, fsdp=args.fsdp)
    state = init_state(jax.random.PRNGKey(0))
    if start:
        state = restore_checkpoint(args.checkpoint_dir, state)
        print(f"resumed from step {start}")

    # Deterministic, resumable, dp-sharded stream with host-side prefetch
    # (restart at --resume replays exactly the batches it would have seen).
    if args.corpus:
        import numpy as np

        from mpi_tpu.data import from_token_file

        # Loud one-time validation: out-of-vocab ids would otherwise be
        # CLAMPED by XLA's gather and train silently on garbage.
        mx = int(np.memmap(args.corpus, dtype=np.uint16, mode="r").max())
        if mx >= cfg.vocab:
            raise SystemExit(
                f"--corpus contains token id {mx} >= vocab {cfg.vocab}; "
                f"re-tokenize or remap the corpus first")
        source = from_token_file(args.corpus, args.batch, args.seq,
                                 dtype="uint16")
    else:
        source = SyntheticLM(cfg.vocab, args.batch, args.seq)
    loader = iter(ShardedLoader(source, mesh=mesh, start_step=start))
    ckpt = AsyncCheckpointer()
    for i in range(start, start + args.steps):
        tokens = next(loader)
        with trace.span("train.step", step=i):
            t0 = time.perf_counter()
            state, loss = step(state, tokens)
            loss = float(loss)
            dt = time.perf_counter() - t0
        print(f"step {i:4d}  loss {loss:.4f}  {dt * 1e3:7.1f} ms")
        if (i + 1) % args.checkpoint_every == 0:
            # Async: the step loop only pays for the HBM->host snapshot;
            # npz encode + rename land on the writer thread.
            ckpt.save(args.checkpoint_dir, state, step=i + 1,
                      max_to_keep=3)
            print(f"checkpointing step {i + 1} (async)")
    ckpt.wait()

    if args.sample:
        import numpy as np

        from mpi_tpu.models import generate

        prompt = ShardedLoader(
            SyntheticLM(cfg.vocab, 1, 8, seed=99)).batch_at(0)
        if args.lora:
            # The adapted model = base + trained deltas, merged once.
            from mpi_tpu.models import merge_lora

            sample_params = merge_lora(lora_base, state["lora"])
        else:
            sample_params = state["params"]
        toks = generate(sample_params, prompt, cfg,
                        max_new_tokens=args.sample)
        print("sampled:", np.asarray(toks)[0].tolist())

    if args.trace:
        nev = trace.dump_chrome_trace(args.trace)
        print(f"wrote {nev} trace events to {args.trace}")


if __name__ == "__main__":
    main()
