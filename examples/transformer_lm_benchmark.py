"""Synthetic causal-LM training benchmark: tokens/s/chip + MFU.

A harness for the transformer stack at sizes of the caller's choosing: one
DP train step over all visible chips, bf16 compute, optional flash
attention (Pallas) and GQA, cost-analysis-derived MFU. Prints ONE JSON line
naming its device. The numbers every PR is held to come from the GPT-2
cells of ``benchmarks/run.py``, not from here.

    python examples/transformer_lm_benchmark.py --dim 2048 --depth 16

On CPU for a smoke run:

    JAX_PLATFORMS=cpu python examples/transformer_lm_benchmark.py \
        --dim 64 --depth 2 --heads 4 --seq-len 128 --batch 2 --steps 3
"""

import argparse
import json
import os
import sys
import time

# self-sufficient from any cwd (`python examples/transformer_lm_benchmark.py`
# puts examples/ on sys.path[0], not the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.run.env_util import install_sigterm_exit
from horovod_tpu.tuning import enable_compile_cache

install_sigterm_exit()  # watchdog SIGTERM -> clean device teardown
enable_compile_cache()  # before the first backend touch

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models import TransformerLM
from horovod_tpu.training import (
    make_jit_train_step, replicate, shard_batch, token_xent)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--batch", type=int, default=8, help="per-chip batch")
    p.add_argument("--dim", type=int, default=2048)
    p.add_argument("--depth", type=int, default=16)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA key/value heads (default: same as --heads)")
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--flash", action="store_true",
                   help="use the Pallas flash-attention kernel")
    p.add_argument("--rope", action="store_true",
                   help="rotary position embeddings instead of a learned table")
    p.add_argument("--mode", choices=["train", "decode"], default="train",
                   help="train: tokens/s/chip + MFU of a DP train step; "
                        "decode: kv-cache generation tokens/s/chip")
    p.add_argument("--prompt-len", type=int, default=512,
                   help="decode mode: prefill length")
    args = p.parse_args()
    if args.steps < 1 or args.warmup < 1 or args.batch < 1:
        p.error("--steps, --warmup and --batch must be >= 1")
    if args.mode == "decode":
        if args.flash:
            p.error("--flash has no effect in decode mode: the kv-cache "
                    "path uses its own single-step attention")
        if args.prompt_len < 1 or args.seq_len <= args.prompt_len:
            p.error("decode mode needs 1 <= --prompt-len < --seq-len")

    hvd.init()
    n_chips = hvd.size()

    attention_fn = None
    if args.flash:
        from horovod_tpu.ops.flash_attention import flash_attention

        attention_fn = flash_attention
    model_kwargs = dict(
        vocab=args.vocab, dim=args.dim, depth=args.depth, heads=args.heads,
        kv_heads=args.kv_heads, max_len=args.seq_len,
        pos_embedding="rope" if args.rope else "learned",
    )
    if attention_fn is not None:
        model_kwargs["attention_fn"] = attention_fn
    model = TransformerLM(**model_kwargs)

    if args.mode == "decode":
        return _run_decode(args, model)

    rng = np.random.RandomState(0)
    global_batch = args.batch * n_chips
    tokens_np = rng.randint(
        0, args.vocab, (global_batch, args.seq_len)).astype(np.int32)
    tokens = shard_batch(tokens_np)
    targets = shard_batch(np.roll(tokens_np, -1, axis=1))

    params = model.init(
        jax.random.PRNGKey(0), jnp.asarray(tokens_np[:1]))["params"]
    tx = hvd.DistributedOptimizer(optax.adamw(1e-4))
    opt_state = replicate(tx.init(params))
    params = replicate(params)

    step = make_jit_train_step(model, tx, loss_fn=token_xent)
    batch_stats = {}  # TransformerLM is stateless

    step = step.lower(
        params, batch_stats, opt_state, tokens, targets).compile()
    ca = step.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    step_flops = float(ca["flops"])

    for _ in range(args.warmup):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, tokens, targets)
    jax.block_until_ready((params, loss))

    from horovod_tpu.profiler import timed_steps

    state = [params, batch_stats, opt_state]

    def run_one():
        state[0], state[1], state[2], loss = step(
            state[0], state[1], state[2], tokens, targets)
        return loss

    losses, dt = timed_steps(run_one, args.steps)
    if not all(np.isfinite(l) for l in losses):
        raise SystemExit(f"non-finite loss: {losses[-3:]}")

    tokens_per_sec = global_batch * args.seq_len * args.steps / dt
    device_kind = jax.devices()[0].device_kind
    result = {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / n_chips, 1),
        "unit": "tokens/s/chip",
        "n_chips": n_chips,
        "platform": jax.devices()[0].platform,
        "device_kind": device_kind,
        "flash": bool(args.flash),
        "rope": bool(args.rope),
    }
    from horovod_tpu.profiler import device_peak_flops

    peak = device_peak_flops(device_kind)  # None off TPU: no MFU on a CPU
    if peak is not None:
        achieved = step_flops * args.steps / dt
        result["mfu"] = round(achieved / (n_chips * peak), 4)
        result["model_tflops_per_step"] = round(step_flops / 1e12, 3)
    print(json.dumps(result))


def _run_decode(args, model):
    """KV-cache generation throughput: warm generate() calls compile the
    prefill + scan, then timed runs. Single-process (decode is per-replica;
    DP replicates it)."""
    from horovod_tpu.models import generate

    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(
        0, args.vocab, (args.batch, args.prompt_len)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), prompt[:, :8])["params"]

    new_tokens = args.seq_len - args.prompt_len

    # generate() is pure -> jit the whole prefill + scan once
    gen = jax.jit(lambda p, pr: generate(
        model, p, pr, max_new_tokens=new_tokens))
    for _ in range(args.warmup):
        out = gen(params, prompt)
    jax.block_until_ready(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = gen(params, prompt)
        _ = int(np.asarray(out[0, -1]))  # host fence
    dt = time.perf_counter() - t0

    result = {
        "metric": "transformer_lm_decode_tokens_per_sec",
        "value": round(args.batch * new_tokens * args.steps / dt, 1),
        "unit": "tokens/s",
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "new_tokens": new_tokens,
        "device_kind": jax.devices()[0].device_kind,
        "rope": bool(args.rope),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
