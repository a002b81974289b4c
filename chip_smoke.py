#!/usr/bin/env python
"""The quickest proof that horovod_tpu still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of ``models.TransformerSmall`` (vocab 32,768,
dim 768, depth 12, heads 12, bf16; sequence 2,048, per-chip batch 4) on
whatever ``jax.devices()`` shows:

1. kernels — every Pallas kernel ``HOROVOD_PALLAS=auto`` arms on TPU, and
   the flash-attention forward/backward, compiled through Mosaic and
   compared with its HLO / scan reference;
2. allreduce — one eager ``hvd.allreduce`` on a stacked array with a
   known answer;
3. train — ``hvd.init`` → ``hvd.broadcast_parameters`` → warm-up and
   timed steps through ``make_shardmap_train_step`` (explicit
   ``hvd.allreduce``, plain optax) and then ``make_jit_train_step`` +
   ``hvd.DistributedOptimizer(optax.adamw)``, both with
   ``attention_fn=flash_attention`` on a learnable synthetic task;
4. serve — the trained weights into ``serving.InferenceEngine`` with a
   page pool sized for the model, ragged greedy requests checked
   token-for-token against ``models.generate()`` (both ``float32``).

With no arguments this is the chip run: it exits non-zero before any
phase unless ``jax.devices()[0].platform == "tpu"``, any failing phase
ends the run non-zero at once, and the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--cpu-rehearsal`` is the same control flow at a toy size with the
kernels in Pallas interpret mode — for debugging the script itself; its
output says ``platform=cpu`` and carries no device number.
"""

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import re
import time


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    dim: int
    depth: int
    heads: int
    seq: int
    batch: int          # per chip
    warmup: int
    steps: int          # timed, per builder
    live_vocab: int     # tokens the synthetic task draws from
    prompt_lens: tuple
    max_new: int
    prefill_chunk: int
    kernel_elems: int   # flat length the vector kernels run at
    mosaic: bool        # kernels compile through Mosaic (False: interpreted)


# TransformerSmall as published in the repo; one mlp_up kernel's worth of
# elements for the flat-vector kernels
FULL = Sizes(vocab=32768, dim=768, depth=12, heads=12, seq=2048, batch=4,
             warmup=2, steps=34, live_vocab=64,
             prompt_lens=(5, 17, 33, 64, 100, 257, 700, 1900), max_new=32,
             prefill_chunk=64, kernel_elems=768 * 3072, mosaic=True)
TINY = Sizes(vocab=512, dim=64, depth=2, heads=4, seq=128, batch=2,
             warmup=1, steps=5, live_vocab=64,
             prompt_lens=(3, 9, 17, 30, 41, 64, 77, 100), max_new=8,
             prefill_chunk=16, kernel_elems=5000, mosaic=False)


def compile_mark():
    """What the program has booked of its compile pipeline so far
    (``profiler.book_compiles``, from ``hvd.init``): the backend's compile
    seconds summed over functions, and the persistent cache's hits and
    misses. Each step builder here builds one step of its name, so no
    rebuild resets a series between two marks."""
    from horovod_tpu.observability import metrics

    snap = metrics.snapshot()

    def total(name, stage=None):
        samples = snap.get(name, {"samples": {}})["samples"]
        return sum(v for k, v in samples.items()
                   if stage is None or f"stage={stage}" in k.split(","))

    return {"compile_s": total("compile_seconds", "compile"),
            "hits": total("compile_cache_hits"),
            "misses": total("compile_cache_misses")}


def compile_since(mark):
    now = compile_mark()
    return {k: now[k] - mark[k] for k in now}


@contextlib.contextmanager
def phase(name):
    """Print one line per phase: wall seconds split into the backend's
    compile (as the program books it) and the rest."""
    print(f"[{name}] start", flush=True)
    mark, t0 = compile_mark(), time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    got = compile_since(mark)
    print(f"[{name}] ok compile_s={got['compile_s']:.1f} "
          f"run_s={wall - got['compile_s']:.1f} "
          f"persistent_cache_hits={got['hits']:.0f} "
          f"misses={got['misses']:.0f}", flush=True)


def check(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


# --------------------------------------------------------------------------
# phase 1: kernels


def _assert_mosaic(fn, args, name, mosaic):
    """Compile ``fn`` and, on the chip, require the Mosaic custom call in
    the optimized HLO — a silent HLO/scan path cannot pass."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    if mosaic:
        check("tpu_custom_call" in compiled.as_text(),
              f"kernel {name}: no tpu_custom_call in the compiled HLO")
    return compiled


def phase_kernels(sz):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu import compression as C
    from horovod_tpu.ops import adasum, pallas_kernels as pk
    from horovod_tpu.models.transformer import default_attention
    from horovod_tpu.ops.flash_attention import flash_attention

    check(pk.enabled(), "HOROVOD_PALLAS=auto did not arm the kernels")
    check(pk.interpret() != sz.mosaic, "kernels would not compile via Mosaic")
    rng = np.random.RandomState(0)
    n, block = 4, C.INT8_BLOCK
    L = sz.kernel_elems
    flat = jnp.asarray(rng.randn(L).astype(np.float32))
    other = jnp.asarray(rng.randn(L).astype(np.float32))

    def run(name, fn, ref_fn, args, compare):
        out = _assert_mosaic(fn, args, name, sz.mosaic)(*args)
        ref = jax.jit(ref_fn)(*args)
        jax.block_until_ready((out, ref))
        detail = compare(out, ref)
        print(f"  kernel {name}: {detail}", flush=True)

    def close(tol, relative=False):
        def cmp(out, ref):
            worst = 0.0
            for o, r in zip(jax.tree_util.tree_leaves(out),
                            jax.tree_util.tree_leaves(ref)):
                o, r = np.asarray(o, np.float32), np.asarray(r, np.float32)
                check(o.shape == r.shape, f"shape {o.shape} != {r.shape}")
                check(np.isfinite(o).all(), "non-finite kernel output")
                scale = float(np.abs(r).max()) if relative else 1.0
                worst = max(worst, float(np.abs(o - r).max()) / scale)
            check(worst <= tol, f"max |kernel - reference| {worst} > {tol}")
            kind = "max_rel_diff" if relative else "max_abs_diff"
            return f"{kind}={worst:.3g} (tol {tol:g})"
        return cmp

    def wire_image(x, unpad=lambda d: d):
        """Comparator for an int8 wire image ``(q, scales[, roundtrip])`` of
        ``x`` (host float64, already in the wire's block layout). Kernel
        and reference must agree bit for bit on the scales and on every
        code but a provable rounding tie — ``x / scale`` within a few
        float32 ulps of a half-integer, where two faithful dividers may
        land on either side, one code apart — and each side's roundtrip
        must be exactly its own ``q * scale``, the value the receiver
        rebuilds."""
        def cmp(out, ref):
            check((np.asarray(out[1]) == np.asarray(ref[1])).all(),
                  "kernel and reference scales differ")
            sc = np.asarray(ref[1], np.float32)[:, None]
            q, q_ref = (np.asarray(o[0], np.int32).reshape(-1, block)
                        for o in (out, ref))
            off = q != q_ref
            t = np.abs(x.reshape(-1, block) / np.where(sc > 0, sc, 1.0))
            tie = np.abs(t - np.floor(t) - 0.5) <= t * 2.0 ** -20
            check((~off | (tie & (np.abs(q - q_ref) == 1))).all(),
                  f"{int((off & ~tie).sum())} code(s) differ away from a "
                  f"rounding tie")
            extra = ""
            if len(out) == 3:
                for name, o in (("kernel", out), ("reference", ref)):
                    deq = np.asarray(o[0], np.float32).reshape(-1, block) * sc
                    check((np.asarray(o[2]) == unpad(deq.reshape(-1))).all(),
                          f"{name} roundtrip is not its own q * scale")
                extra = ", both roundtrips == q * scale"
            return (f"scales exact, codes exact but {int(off.sum())} "
                    f"rounding tie(s) of {q.size}{extra}")
        return cmp

    x64 = np.asarray(flat, np.float64)
    pad = (-L) % block
    run("quantize_blockwise",
        lambda x: C.quantize_blockwise(x),
        lambda x: C.quantize_blockwise(x, use_pallas=False),
        (flat,), wire_image(np.pad(x64, (0, pad))))
    s_chunk = L // n
    sp = -(-s_chunk // block) * block
    run("quantize_roundtrip",
        lambda x: C.quantize_chunked(x[:n * s_chunk], n),
        lambda x: C.quantize_chunked(x[:n * s_chunk], n, use_pallas=False),
        (flat,), wire_image(
            np.pad(x64[:n * s_chunk].reshape(n, s_chunk),
                   ((0, 0), (0, sp - s_chunk))),
            lambda d: d.reshape(n, sp)[:, :s_chunk].reshape(-1)))

    # the post-all_to_all wire image: N senders' int8 chunks + bf16 scales
    qr = jnp.asarray(rng.randint(-127, 128, (n, sp)).astype(np.int8))
    scr = jnp.asarray(
        (np.abs(rng.randn(n, sp // block)) * 0.01).astype(np.float32)
    ).astype(jnp.bfloat16)

    def deq_sum(q, s):
        return C.dequantize_blockwise(
            q.reshape(-1), s.reshape(-1), jnp.float32).reshape(n, sp).sum(0)

    run("dequant_accumulate",
        lambda q, s: pk.dequant_accumulate(q, s, jnp.float32, block),
        deq_sum, (qr, scr), close(1e-4))
    run("dequant_accumulate_requantize",
        lambda q, s: pk.dequant_accumulate_requantize(
            q, s, jnp.float32, block, divisor=n),
        lambda q, s: C.quantize_blockwise(deq_sum(q, s) / n,
                                          use_pallas=False),
        (qr, scr), wire_image(
            (np.asarray(qr, np.float64).reshape(n, -1, block)
             * np.asarray(scr, np.float64)[:, :, None]).sum(0) / n))
    run("dequantize_rows",
        lambda q, s: C.dequantize_rows(q, s, jnp.float32),
        lambda q, s: C.dequantize_rows(q, s, jnp.float32, use_pallas=False),
        (qr, scr), close(0.0))

    knob = os.environ.get(pk.PALLAS_ENV)

    def hlo(fn):
        """The call site's own HLO branch: the same function traced with
        the kernels disarmed."""
        def ref(*args):
            os.environ[pk.PALLAS_ENV] = "0"
            try:
                return fn(*args)
            finally:
                if knob is None:
                    del os.environ[pk.PALLAS_ENV]
                else:
                    os.environ[pk.PALLAS_ENV] = knob
        return ref

    run("adasum_pair_combine", adasum._pair_combine,
        hlo(adasum._pair_combine), (flat, other), close(1e-4))
    sizes = [L // 2, 1, L // 3, L - L // 2 - 1 - L // 3]
    seg = jnp.asarray(np.repeat(np.arange(len(sizes)), sizes), jnp.int32)
    run("adasum_segment_combine",
        lambda a, b, s: adasum._segment_combine(a, b, s, len(sizes)),
        hlo(lambda a, b, s: adasum._segment_combine(a, b, s, len(sizes))),
        (flat, other, seg), close(1e-4))

    # fused Adam: plain, and the vmapped [N, shard] form optim._zero_update
    # applies per bucket
    import optax

    from horovod_tpu.optim import fused_adam

    fa, ref_adam = fused_adam(1e-3), optax.adam(1e-3)
    run("fused_adam_update", fa.update, ref_adam.update,
        (flat, ref_adam.init(flat)), close(1e-6))
    shards = flat[:L - L % n].reshape(n, -1)
    run("fused_adam_update[vmap]", jax.vmap(fa.update),
        jax.vmap(ref_adam.update), (shards, jax.vmap(ref_adam.init)(shards)),
        close(1e-6))

    # flash attention at the model's own shape, and a 128-wide GQA head,
    # against dense softmax attention in float32
    hd = sz.dim // sz.heads
    flash = functools.partial(flash_attention, causal=True,
                              use_pallas=True, interpret=not sz.mosaic)

    def dense(q, k, v):
        return default_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                                 causal=True)

    def grads(att):
        return jax.grad(lambda q, k, v: jnp.sum(
            att(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))

    for name, (b, h, h_kv, d) in (
            (f"flash[mha d{hd}]", (sz.batch, sz.heads, sz.heads, hd)),
            ("flash[gqa d128]", (2, 4, 2, 128))):
        q, k, v = (jnp.asarray(rng.randn(b, sz.seq, hh, d), jnp.bfloat16)
                   for hh in (h, h_kv, h_kv))
        run(name + " fwd", flash, dense, (q, k, v), close(0.02, True))
        run(name + " fwd+bwd", grads(flash), grads(dense), (q, k, v),
            close(0.03, True))

    # the routed-expert layer (top-2 of 8 experts, all held), bfloat16
    # products: the layer and every gradient against a dense per-expert
    # loop in float32
    from horovod_tpu import metrics
    from horovod_tpu.parallel import moe

    def routed(x, router, gate, up, down):
        return moe.routed_experts(x, router, gate, up, down, top_k=2,
                                  dtype=jnp.bfloat16,
                                  interpret=not sz.mosaic)[0]

    def dense_experts(x, router, gate, up, down):
        weights, chosen = moe.route_top_k(x, router, 2)
        with jax.default_matmul_precision("highest"):
            return sum(
                jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)[:, None]
                * ((jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
                for e in range(gate.shape[0]))

    def layer_and_grads(layer):
        return jax.value_and_grad(lambda *a: jnp.sum(
            layer(*a).astype(jnp.float32) ** 2), argnums=range(5))

    # x as bfloat16 rounds it: the layer rounds its copy, the router reads
    # it as it came
    layer_args = [jnp.asarray(rng.randn(*shape) * scale, jnp.float32)
                  for shape, scale in (((1024, 256), 1.0), ((256, 8), 0.1),
                                       ((8, 256, 128), 0.1),
                                       ((8, 256, 128), 0.1),
                                       ((8, 128, 256), 0.1))]
    layer_args[0] = layer_args[0].astype(jnp.bfloat16).astype(jnp.float32)
    run("routed_experts fwd+bwd", layer_and_grads(routed),
        layer_and_grads(dense_experts), layer_args, close(0.03, True))
    print(f"  moe_experts_fused={metrics.value('moe_experts_fused')} "
          f"moe_combine_tile="
          f"{metrics.value('moe_combine_tile', dim='tokens')}", flush=True)


# --------------------------------------------------------------------------
# phase 2: eager allreduce


def phase_allreduce(hvd):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = hvd.size()
    rows = np.arange(1, n + 1, dtype=np.float32)[:, None] * np.ones(
        (n, 1024), np.float32)
    stacked = jax.device_put(
        rows, NamedSharding(hvd.mesh(), P(hvd.data_axis())))
    total = np.asarray(hvd.allreduce(stacked, op=hvd.Sum))
    mean = np.asarray(hvd.allreduce(stacked))
    check(total.shape == (1024,) and (total == n * (n + 1) / 2).all(),
          f"allreduce Sum over {n} ranks gave {total[:4]}")
    check((mean == (n + 1) / 2).all(),
          f"allreduce Average over {n} ranks gave {mean[:4]}")
    print(f"  eager allreduce over {n} rank(s): sum={total[0]:g} "
          f"mean={mean[0]:g}", flush=True)


# --------------------------------------------------------------------------
# phase 3: train


def teacher_batches(sz, n_chips, count, seed):
    """A learnable task, not random labels: every sequence walks one
    fixed full-period affine map over ``live_vocab`` tokens, so the next
    token is a function of the current one. Rows start at random points
    of the cycle, so every chip sees different data."""
    import numpy as np

    rng = np.random.RandomState(seed)
    v = sz.live_vocab
    cycle = np.zeros(v, np.int64)
    for i in range(1, v):
        cycle[i] = (5 * cycle[i - 1] + 3) % v
    out = []
    for _ in range(count):
        start = rng.randint(0, v, (sz.batch * n_chips, 1))
        walk = cycle[(start + np.arange(sz.seq + 1)) % v].astype(np.int32)
        out.append((walk[:, :-1], walk[:, 1:]))
    return out


def _train_builder(name, step, state, batches, sz, hvd):
    """Warm-up then timed steps through one step builder; returns the
    trained params and every step's loss."""
    import jax
    import numpy as np

    params, opt_state = state
    n = hvd.size()
    hd, b_local = sz.dim // sz.heads, sz.batch
    tokens, targets = batches[0]
    hlo = step.lower(params, {}, opt_state, tokens, targets).compile().as_text()
    if sz.mosaic:
        check("tpu_custom_call" in hlo,
              f"{name}: compiled train step holds no Mosaic call — the "
              f"flash kernel did not run")
        # the kernel must see this chip's batch shard, not the gathered
        # global batch
        rows = {m for line in hlo.splitlines() if "tpu_custom_call" in line
                for m in re.findall(r"bf16\[(\d+),%d,%d\]" % (sz.seq, hd),
                                    line)}
        check(rows == {str(b_local * sz.heads)},
              f"{name}: flash kernel operand rows {sorted(rows)}, expected "
              f"{b_local * sz.heads} (= per-chip batch x heads)")
    if n > 1:
        check("all-reduce" in hlo, f"{name}: no all-reduce in the step HLO")
        check("all-gather" not in hlo,
              f"{name}: the DP step all-gathers — activations are being "
              f"replicated around an opaque kernel")

    losses = []
    for tokens, targets in batches[:sz.warmup]:
        params, _, opt_state, loss = step(params, {}, opt_state, tokens,
                                          targets)
        losses.append(float(loss))
    mark, t0 = compile_mark(), time.perf_counter()
    timed = []
    for tokens, targets in batches[sz.warmup:]:
        params, _, opt_state, loss = step(params, {}, opt_state, tokens,
                                          targets)
        timed.append(loss)
    jax.block_until_ready((params, timed))
    dt = time.perf_counter() - t0
    built_s = compile_since(mark)["compile_s"]
    check(built_s == 0, f"{name}: {built_s:.2f} s of compilation inside the "
                        f"timed steps")
    losses += [float(x) for x in timed]
    check(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{name}: loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    per_step = dt / len(timed)
    print(f"  {name}: loss {losses[0]:.4f} -> {losses[-1]:.4f} over "
          f"{len(losses)} steps; {len(timed)} timed steps "
          f"{per_step * 1e3:.1f} ms/step, "
          f"{sz.batch * sz.seq / per_step:,.0f} tokens/s/chip, "
          f"0 compilations in the window", flush=True)
    return params, losses


def phase_train(sz, hvd, model):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.training import (
        make_jit_train_step, make_shardmap_train_step, replicate,
        shard_batch, token_xent)

    n = hvd.size()
    host = teacher_batches(sz, n, sz.warmup + sz.steps, seed=0)
    batches = [(shard_batch(x), shard_batch(y)) for x, y in host]
    if n > 1:
        tok = batches[0][0]
        devs = {s.device for s in tok.addressable_shards}
        check(len(devs) == n, f"batch shards sit on {len(devs)} device(s)")
        first = [np.asarray(s.data) for s in tok.addressable_shards]
        check(all((first[0] != f).any() for f in first[1:]),
              "every chip was fed the same data")

    init = jax.device_get(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.asarray(host[0][0][:1]))["params"])
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(init))
    print(f"  model: {n_params / 1e6:.1f}M parameters, global batch "
          f"{sz.batch * n} x {sz.seq} tokens on {n} chip(s)", flush=True)

    # Both builders start from the same broadcast parameters and see the
    # same batches, so their first losses must agree.
    # builder 1: the literal Horovod step — shard_map, hvd.allreduce on
    # every gradient (lowers to psum), plain optax
    lr = 3e-4
    tx = optax.adamw(lr)
    params = replicate(hvd.broadcast_parameters(init))
    _, explicit = _train_builder(
        "make_shardmap_train_step",
        make_shardmap_train_step(model, tx, loss_fn=token_xent),
        (params, replicate(tx.init(params))), batches, sz, hvd)

    # builder 2: one global jit + DistributedOptimizer — what the one-chip
    # benchmark cells and examples/transformer_lm_benchmark.py run
    dtx = hvd.DistributedOptimizer(optax.adamw(lr))
    params = replicate(hvd.broadcast_parameters(init))
    params, global_jit = _train_builder(
        "make_jit_train_step+DistributedOptimizer",
        make_jit_train_step(model, dtx, loss_fn=token_xent),
        (params, replicate(dtx.init(params))), batches, sz, hvd)
    check(abs(explicit[0] - global_jit[0]) <= 1e-3 * explicit[0],
          f"the two builders disagree on the first loss: {explicit[0]} vs "
          f"{global_jit[0]}")

    if n > 1:
        for leaf in jax.tree_util.tree_leaves(params):
            copies = [np.asarray(s.data) for s in leaf.addressable_shards]
            check(len(copies) == n and all(
                (copies[0] == c).all() for c in copies[1:]),
                "parameters differ between chips after the step")
    # evaluation on ONE chip of the host, the last one: the flash model
    # on arrays committed there runs there, whatever mesh hvd holds
    dev = jax.devices()[-1]
    x, y = (jax.device_put(a[:sz.batch], dev) for a in host[0])
    eval_loss = jax.jit(lambda w, x, y: token_xent(
        model.apply({"params": w}, x, train=False), y))(
            jax.device_put(params, dev), x, y)
    check(eval_loss.devices() == {dev},
          f"single-chip evaluation ran on {eval_loss.devices()}, not {dev}")
    check(float(eval_loss) < global_jit[0],
          f"single-chip evaluation loss {float(eval_loss):.4f} is not below "
          f"the {global_jit[0]:.4f} the training started from")
    print(f"  single-chip evaluation on {dev}: loss {float(eval_loss):.4f}",
          flush=True)
    peak = jax.devices()[0].memory_stats()
    if peak:
        print(f"  peak_bytes_in_use={peak['peak_bytes_in_use']:,} "
              f"(per-chip batch {sz.batch})", flush=True)
    return jax.device_get(params)


# --------------------------------------------------------------------------
# phase 4: serve


def phase_serve(sz, model, params):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import generate
    from horovod_tpu.models.transformer import default_attention
    from horovod_tpu.serving.engine import InferenceEngine

    # the trained weights under a float32 twin of the model: engine and
    # generate() must then agree token for token (in bf16 a near-tie can
    # legitimately flip between the two batch shapes)
    serve_model = dataclasses.replace(
        model, dtype=jnp.float32, attention_fn=default_attention)
    max_batch, page_size = len(sz.prompt_lens), 16
    pages_per_seq = -(-sz.seq // page_size)
    eng = InferenceEngine(
        serve_model, page_size=page_size,
        num_pages=max_batch * pages_per_seq + 1, max_batch=max_batch,
        prefill_chunk=sz.prefill_chunk, max_seq_len=sz.seq)
    eng.set_weights(params)
    print(f"  engine: one replica on {jax.devices()[0]} (by design — "
          f"replicas scale out through serving.FleetRouter), pool "
          f"{eng.num_pages} pages x {page_size} tokens, batch {max_batch}",
          flush=True)

    x, _ = teacher_batches(
        dataclasses.replace(sz, batch=max_batch), 1, 1, seed=1)[0]
    prompts = [x[i, :n] for i, n in enumerate(sz.prompt_lens)]
    t0 = time.perf_counter()
    reqs = [eng.submit(p, sz.max_new, rid=f"smoke-{i}")
            for i, p in enumerate(prompts)]
    eng.run_until_idle()
    dt = time.perf_counter() - t0
    for r, p in zip(reqs, prompts):
        check(r.error is None, f"request {r.rid}: {r.error}")
        check(r.tokens is not None and r.tokens.size == p.size + sz.max_new,
              f"request {r.rid} returned "
              f"{None if r.tokens is None else r.tokens.size} tokens, "
              f"expected {p.size + sz.max_new}")

    t_max = max(sz.prompt_lens)
    padded = np.zeros((max_batch, t_max), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :p.size] = p
    ref = np.asarray(jax.jit(lambda w, pr, pl: generate(
        serve_model, w, pr, max_new_tokens=sz.max_new, prompt_lens=pl,
    ))(params, jnp.asarray(padded), jnp.asarray(sz.prompt_lens, jnp.int32)))
    for i, (r, p) in enumerate(zip(reqs, prompts)):
        want = ref[i, p.size:p.size + sz.max_new]
        got = r.tokens[p.size:]
        check((got == want).all(),
              f"request {r.rid} (prompt {p.size}): engine {got.tolist()} "
              f"!= generate() {want.tolist()}")
    total = sum(sz.prompt_lens) + sz.max_new * max_batch
    print(f"  {len(reqs)} ragged requests (prompts {min(sz.prompt_lens)}.."
          f"{t_max} tokens, {sz.max_new} new each) served in {dt:.1f}s "
          f"incl. compile, {total} tokens; greedy output token-identical "
          f"to generate() with dtype=float32", flush=True)


# --------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="toy-size dry run of this script on the CPU backend with "
             "interpret-mode kernels; proves nothing about the chip")
    args = ap.parse_args(argv)

    from horovod_tpu import tuning

    cache = tuning.enable_compile_cache()  # before the first backend touch
    if args.cpu_rehearsal:
        os.environ["HOROVOD_PALLAS"] = "1"  # interpret mode off TPU

    import jax

    import horovod_tpu as hvd
    from horovod_tpu import models, profiler
    from horovod_tpu.ops.flash_attention import flash_attention

    # the first backend touch: init applies HOROVOD_XLA_FLAGS_PRESET, which
    # the runtime reads once, before it creates the backend
    hvd.init()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if args.cpu_rehearsal == on_tpu:
        raise SystemExit(
            f"chip_smoke: platform={dev.platform} — "
            + ("--cpu-rehearsal is for hosts without a chip"
               if on_tpu else
               "no accelerator; the chip run needs a TPU (run it through "
               "the chip tool, or pass --cpu-rehearsal to debug the script)"))

    peak = profiler.device_peak_flops(dev.device_kind)  # raises if unknown
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"devices={len(jax.devices())} jax={jax.__version__} "
          f"peak_bf16_flops={peak} compile_cache="
          f"{cache or os.environ['JAX_COMPILATION_CACHE_DIR']} "
          f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}",
          flush=True)
    sz = TINY if args.cpu_rehearsal else FULL
    t_start = time.perf_counter()

    n = hvd.size()
    check(n == jax.device_count(),
          f"hvd.size()={n} but jax sees {jax.device_count()} devices")
    model = models.TransformerSmall(
        vocab=sz.vocab, dim=sz.dim, depth=sz.depth, heads=sz.heads,
        max_len=sz.seq,
        attention_fn=functools.partial(
            flash_attention, use_pallas=True, interpret=not sz.mosaic))

    with phase("kernels"):
        phase_kernels(sz)
    with phase("allreduce"):
        phase_allreduce(hvd)
    with phase("train"):
        params = phase_train(sz, hvd, model)
    with phase("serve"):
        phase_serve(sz, model, params)
    hvd.shutdown()

    total = compile_mark()
    steps = {k: v for k, v in hvd.metrics.snapshot()["compile_seconds"][
        "samples"].items() if "fn=other" not in k.split(",")}
    print(f"total wall_s={time.perf_counter() - t_start:.1f} "
          f"compile_s={total['compile_s']:.1f} "
          f"persistent_cache_hits={total['hits']:.0f} "
          f"misses={total['misses']:.0f}; the steps' latest builds "
          f"{ {k: round(v, 2) for k, v in steps.items()} }", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }), flush=True)


if __name__ == "__main__":
    main()
