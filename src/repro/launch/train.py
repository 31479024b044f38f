"""Training driver: streaming micro-batch LM training on the Spark-MPI stack.

The paper's pattern end-to-end: a token producer appends micro-batches to
the broker; the StreamingContext discretizes them into batch RDDs; each
batch becomes one collective train step on the mesh (the "MPI application");
checkpoints are sharded+async; crash/elastic restart resumes from offsets +
checkpoint.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --steps 50 --batch 4 --seq 128 --ckpt-dir /tmp/ck

--reduced runs the real loop on the reduced config; full-scale configs need
a mesh of their size.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.configs import get_config
from repro.configs.base import OptimizerConfig
from repro.core import Broker, Context, StreamingContext
from repro.training import build_train_step, init_state
from repro.utils import get_logger, tree_any_nan

log = get_logger(__name__)


def synthetic_producer(broker: Broker, config, steps: int, batch: int,
                       seq: int, seed: int = 0) -> None:
    """Stands in for the detector/corpus: one record per sequence."""
    rng = np.random.default_rng(seed)
    for _ in range(steps * batch):
        rec = {"tokens": rng.integers(
            0, config.vocab_size, (seq,), dtype=np.int32)}
        if config.family == "vlm":
            rec["image_embeds"] = rng.standard_normal(
                (config.num_image_tokens, config.d_model)).astype(np.float32)
        if config.family == "audio":
            rec["frames"] = rng.standard_normal(
                (config.encoder_seq, config.d_model)).astype(np.float32)
        broker.produce("tokens", rec)


def assemble_batch(records: list[dict], config) -> dict:
    batch = {"tokens": jnp.asarray(np.stack([r["tokens"] for r in records]))}
    if config.family == "vlm":
        batch["image_embeds"] = jnp.asarray(
            np.stack([r["image_embeds"] for r in records]), jnp.bfloat16)
    if config.family == "audio":
        batch["frames"] = jnp.asarray(
            np.stack([r["frames"] for r in records]), jnp.bfloat16)
    return batch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    config = get_config(args.arch, reduced=args.reduced)
    if config.family == "vlm" and args.seq <= config.num_image_tokens:
        args.seq = config.num_image_tokens + args.seq
    opt = OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                          total_steps=args.steps, zero1=False)

    # data plane: broker + streaming context
    broker = Broker()
    broker.create_topic("tokens", partitions=2)
    synthetic_producer(broker, config, args.steps, args.batch, args.seq,
                       args.seed)
    ctx = Context()
    sc = StreamingContext(ctx, broker,
                          max_records_per_partition=args.batch,
                          checkpoint_path=(f"{args.ckpt_dir}/offsets.json"
                                           if args.ckpt_dir else None))
    sc.subscribe(["tokens"])

    # compute plane
    state = init_state(jax.random.PRNGKey(args.seed), config, opt)
    start_step = 0
    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, start_step = restore(args.ckpt_dir,
                                    jax.eval_shape(lambda: state))
        log.info("resumed from step %d", start_step)
    step_fn = jax.jit(build_train_step(config, opt), donate_argnums=(0,))

    stats = {"step": start_step, "state": state, "t0": time.time(),
             "tokens": 0}

    def on_batch(rdd, info):
        records = rdd.collect()[: args.batch]
        if len(records) < args.batch:
            return None
        batch = assemble_batch(records, config)
        stats["state"], metrics = step_fn(stats["state"], batch)
        stats["step"] += 1
        stats["tokens"] += int(np.prod(batch["tokens"].shape))
        s = stats["step"]
        if s % args.log_every == 0 or s == start_step + 1:
            dt = time.time() - stats["t0"]
            log.info("step %d loss %.4f lr %.2e gnorm %.2f | %.0f tok/s",
                     s, float(metrics["loss"]), float(metrics["lr"]),
                     float(metrics["grad_norm"]), stats["tokens"] / dt)
        if ckpt and s % args.ckpt_every == 0:
            ckpt.save(s, stats["state"])
        return float(metrics["loss"])

    sc.foreach_batch(on_batch)
    while stats["step"] < start_step + args.steps:
        if sc.run_one_batch() is None:
            break
    if ckpt:
        ckpt.save(stats["step"], stats["state"])
        ckpt.wait()
    if tree_any_nan(stats["state"]["params"]):
        raise SystemExit("NaN in parameters")
    rep = sc.realtime_report()
    log.info("done: %d steps, %.0f rec/s, mean batch %.3fs",
             stats["step"], rep.get("throughput_rec_per_s", 0),
             rep.get("mean_processing_s", 0))


if __name__ == "__main__":
    main()
