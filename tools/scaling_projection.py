"""Reference models for the tests: wire bytes and token counts from shapes.

Each function here computes, from parameter shapes, world size and the
documented wire format alone, a count that the program also reports through
a live counter or gauge; a tier-1 test compares the two to equality
(``tests/test_fsdp.py``, ``test_prefix_spec.py``, ``test_serving.py``,
``test_pallas.py``, ``test_scaling_projection.py``). They are independent
re-statements of the wire formats, not measurements: there is no time, rate
or hardware figure in this file. What a path costs on the chip is read by a
paired run in a benchmark cell (``python3 benchmarks/run.py --workload ...``).
"""

import math
from typing import Optional

import numpy as np

def zero1_sync_bytes(grad_bytes: float, n: int, *, wire_bytes: float = None,
                     update_bytes: float = None) -> dict:
    """Ring byte model for the DP gradient exchange, allreduce vs the ZeRO-1
    reduce-scatter -> all-gather decomposition
    (``DistributedOptimizer(shard_optimizer=True)``):

    - allreduce moves ``2(N-1)/N·B`` gradient bytes per step;
    - sharded moves ``(N-1)/N·B`` gradient bytes (the reduce-scatter — half)
      plus ``(N-1)/N·P`` parameter-update bytes (the all-gather).

    With ``wire_bytes`` (compressed gradient volume, e.g. bf16 = B/2) the
    asymmetry shows up: the RS leg rides the wire dtype while the AG leg
    carries full-precision updates — sharded+fp16 moves
    ``(N-1)/N·(B/2 + P)`` vs allreduce+fp16's ``2(N-1)/N·B/2``. These are
    the numbers ``grad_sync_bytes_per_step`` / ``param_gather_bytes_per_step``
    report from the live step (``horovod_tpu.optim._record_sync_bytes``)."""
    w = grad_bytes if wire_bytes is None else wire_bytes
    u = grad_bytes if update_bytes is None else update_bytes
    ring = (n - 1) / n if n > 1 else 0.0
    return {
        "allreduce": 2.0 * ring * w,
        "rs": ring * w,
        "ag": ring * u,
        "sharded_total": ring * (w + u),
    }


def _as_shapes(shapes):
    """Normalize the byte-model input: an int is one flat leaf, a single
    shape tuple is one leaf, else an iterable of shape tuples."""
    if isinstance(shapes, (int, np.integer)):
        return [(int(shapes),)]
    shapes = list(shapes)
    if shapes and isinstance(shapes[0], int):
        return [tuple(shapes)]
    return [tuple(s) for s in shapes]


def _int8_leaf_bytes(size: int, block: int, scale_bytes: int,
                     itemsize: int, min_elems: int) -> int:
    if size < min_elems:  # below the quantize floor: rides uncompressed
        return size * itemsize
    return size + -(-size // block) * scale_bytes


def int8_sync_bytes(shapes, n: int, *, block: int = 256,
                    scale_bytes: int = 2, itemsize: int = 4,
                    min_elems: int = 1024) -> dict:
    """Ring byte model for blockwise int8 gradient compression
    (``Compression.int8``): each float leaf costs ``size * 1`` int8 bytes
    plus ``ceil(size / block) * scale_bytes`` bf16 scales per wire
    direction; leaves below ``min_elems`` (the compressor's
    ``min_quant_elems`` floor — the ring's per-chunk block padding would
    cost more than fp32 there) ride uncompressed at ``itemsize``. This is
    the same per-leaf pricing the live step's ``Compressor.wire_bytes``
    hook reports into ``grad_sync_bytes_per_step``. ``shapes`` is an int
    (one flat leaf), a shape tuple, or a list of shape tuples (per-leaf
    ceil matters)."""
    shapes = _as_shapes(shapes)
    elems = sum(int(np.prod(s, dtype=np.int64)) for s in shapes)
    wire = sum(
        _int8_leaf_bytes(int(np.prod(s, dtype=np.int64)), block,
                         scale_bytes, itemsize, min_elems)
        for s in shapes
    )
    dense = elems * itemsize
    ring = (n - 1) / n if n > 1 else 0.0
    return {
        "allreduce": 2.0 * ring * wire,
        "rs": ring * wire,
        "fp32_allreduce": 2.0 * ring * dense,
        "wire_bytes": wire,
        "ratio_vs_fp32": wire / dense if dense else 0.0,
    }


def fsdp_gather_wire_bytes(shapes, n: int, *, wire: str = "none",
                           block: int = 256, scale_bytes: int = 2,
                           itemsize: int = 4,
                           min_elems: int = 1024) -> int:
    """Wire image of ONE ZeRO-3 parameter all-gather over a single packed
    group (one dtype, no bucket splitting — price a bucketed plan by
    calling this once per bucket). The flat pack pads the group to a
    multiple of N (``Lp = L + (-L) % N``); the fp wire moves ``Lp *
    itemsize``. The int8 wire quantizes each rank's shard blockwise
    before the gather, so every rank's block-padded shard travels as int8
    plus one bf16 scale per block, times N ranks; groups under the
    ``min_elems`` quantize floor ride uncompressed. Analytic twin of
    ``horovod_tpu.optim._fsdp_gather_wire_bytes`` — a test pins them
    equal against the live ``param_gather_bytes_per_step`` gauge."""
    shapes = _as_shapes(shapes)
    size = sum(int(np.prod(s, dtype=np.int64)) for s in shapes)
    lp = size + (-size) % n
    if wire == "int8" and lp >= min_elems:
        s = lp // n
        sp = s + (-s) % block
        return n * (sp + (sp // block) * scale_bytes)
    return lp * itemsize


def zero3_sync_bytes(shapes, n: int, *, wire: str = "none",
                     gathers_per_step: int = 2, block: int = 256,
                     scale_bytes: int = 2, itemsize: int = 4,
                     min_elems: int = 1024) -> dict:
    """Ring byte model for ZeRO-3 gather-on-use
    (``DistributedOptimizer(shard_params=True)`` /
    ``make_shardmap_train_step(shard_params=True)``):

    - the parameter all-gather moves ``(N-1)/N · G`` bytes and runs
      **twice** per step (forward gather-on-use, then the
      ``jax.checkpoint`` re-gather in backward — rematerialization trades
      a second gather for not holding the full params live);
    - gradients reduce-scatter once at ``(N-1)/N · B`` in full precision
      (the int8 knob compresses only the gather leg — the gradient leg
      stays exact, which is what keeps the fp32 trajectory bit-identical
      to ZeRO-1).

    ``zero1_total`` is the same model's ZeRO-1 cost (RS + AG of the same
    parameter volume, once each) — ZeRO-3 loses on pure wire bytes
    whenever ``gathers_per_step · G_wire > G``: with the fp32 wire that
    is always (3 legs vs 2); the int8 wire breaks even near G_wire ≈ G/2
    and wins below. What ZeRO-3 buys instead is **memory** — params live
    ``1/N``-sharded between uses. These are the numbers the live
    ``grad_sync_bytes_per_step{mode="zero3"}`` /
    ``param_gather_bytes_per_step{mode="zero3"}`` gauges report
    (``horovod_tpu.optim._fsdp_update``)."""
    shapes = _as_shapes(shapes)
    size = sum(int(np.prod(s, dtype=np.int64)) for s in shapes)
    lp = size + (-size) % n
    gw = fsdp_gather_wire_bytes(
        shapes, n, wire=wire, block=block, scale_bytes=scale_bytes,
        itemsize=itemsize, min_elems=min_elems)
    rw = lp * itemsize  # gradient leg: always full precision
    ring = (n - 1) / n if n > 1 else 0.0
    return {
        "param_gather": ring * gathers_per_step * gw,
        "grad_reduce_scatter": ring * rw,
        "zero3_total": ring * (gathers_per_step * gw + rw),
        "zero1_total": 2.0 * ring * lp * itemsize,
        "gather_wire_bytes": gw,
    }


def powersgd_sync_bytes(shapes, rank: int, n: int, *, block: int = 256,
                        scale_bytes: int = 2, itemsize: int = 4,
                        min_elems: int = 1024) -> dict:
    """Ring byte model for PowerSGD rank-``r`` compression
    (``Compression.powersgd(rank)``): a >=2-D leaf ``[d0, *rest]`` syncs
    ``(d0 + prod(rest)) * min(rank, d0, prod(rest))`` f32 factor elements
    (P + Q, each a full ring allreduce — hence the 2(N−1)/N factor on the
    whole sum); 1-D leaves ride the int8 fallback (dense below its
    ``min_elems`` floor). Mirrors the live ``wire_bytes`` hook exactly, so
    the model == the gauge."""
    shapes = _as_shapes(shapes)
    factor = 0
    fallback = 0
    dense = 0
    for s in shapes:
        size = int(np.prod(s, dtype=np.int64))
        dense += size * itemsize
        d0 = int(s[0]) if len(s) >= 2 else 0
        m = int(np.prod(s[1:], dtype=np.int64)) if len(s) >= 2 else 0
        r = min(rank, d0, m)
        # factorize only when the factors beat the dense leaf (the live
        # compressor's crossover rule); else the int8/dense fallback
        if len(s) >= 2 and (d0 + m) * r < d0 * m:
            factor += (d0 + m) * r * itemsize
        else:
            fallback += _int8_leaf_bytes(size, block, scale_bytes,
                                         itemsize, min_elems)
    wire = factor + fallback
    ring = (n - 1) / n if n > 1 else 0.0
    return {
        "allreduce": 2.0 * ring * wire,
        "factor_bytes": factor,
        "int8_fallback_bytes": fallback,
        "fp32_allreduce": 2.0 * ring * dense,
        "wire_bytes": wire,
        "ratio_vs_fp32": wire / dense if dense else 0.0,
    }


def pallas_hot_path_bytes(shapes, n: int, *, block: int = 256,
                          scale_bytes: int = 2, itemsize: int = 4,
                          error_feedback: bool = True,
                          epilogue: str = "scatter") -> dict:
    """Analytic HBM-traffic model of the int8 wire hot path, discrete HLO
    vs the fused Pallas kernels (``HOROVOD_PALLAS``), for one flat packed
    gradient buffer of ``E`` f32 elements exchanged over ``n`` ranks.
    Wire (ICI/DCN) bytes are identical by construction — Pallas replaces
    elementwise HLO, never collectives — so this model counts only the
    HBM round-trips *between* the collectives:

    discrete (``q`` = ``E + ceil(E/block)*scale_bytes`` wire-image bytes):

    - EF roundtrip (when ``error_feedback``): the separate
      ``quantize_roundtrip_chunked`` pass — read 4E, write q, read q,
      write 4E;
    - quantize for the wire: read 4E, write q (the corrected buffer is
      read a SECOND time);
    - dequantize: read q, write 4E (the ``[N, sp]`` f32 matrix
      materialized post-``all_to_all``);
    - accumulate: read 4E, write 4E/n;
    - requantize (``epilogue="allreduce"`` only): read 4E/n, write q/n;
    - Adam on the shard (S = E/n): the optax chain's mu/nu/mu_hat/nu_hat
      /prescale/update materializations — 56·4·S/4 bytes un-fused. XLA's
      elementwise fusion recovers much of this stage in practice; the
      model bounds the win.

    fused:

    - quantize kernel: read 4E, write q (+ write 4E roundtrip when EF —
      ONE pass serves the wire and the residual);
    - dequant-accumulate(-requantize) kernel: read q, write 4E/n
      (scatter) or q/n (allreduce) — no f32 matrix, no shard round-trip;
    - fused Adam kernel: read 12S, write 12S.
    """
    if epilogue not in ("scatter", "allreduce"):
        raise ValueError(f"epilogue must be scatter|allreduce, got "
                         f"{epilogue!r}")
    shapes = _as_shapes(shapes)
    e = sum(int(np.prod(s, dtype=np.int64)) for s in shapes)
    f = e * itemsize                                # f32 buffer bytes
    q = e + -(-e // block) * scale_bytes            # wire-image bytes
    s_bytes = f / max(n, 1)                         # one shard, f32
    discrete = {
        "quantize": f + q,
        "dequantize": q + f,
        "accumulate": f + s_bytes,
        "adam_shard": 56 * s_bytes / 4,
    }
    fused = {
        "quantize": f + q,
        "dequant_accumulate": q + s_bytes,
        "adam_shard": 24 * s_bytes / 4,
    }
    if error_feedback:
        discrete["ef_roundtrip"] = 2 * f + 2 * q
        fused["quantize"] += f                      # the fused rt write
    if epilogue == "allreduce":
        discrete["requantize"] = s_bytes + q / n
        fused["dequant_accumulate"] = q + q / n
    d_total = sum(discrete.values())
    f_total = sum(fused.values())
    return {
        "elems": e,
        "n": n,
        "wire_bytes": q,
        "discrete": discrete,
        "fused": fused,
        "discrete_bytes": d_total,
        "fused_bytes": f_total,
        "savings_ratio": (d_total - f_total) / d_total if d_total else 0.0,
    }


def publish_bytes(shapes, *, keyframe_every: int = 8, block: int = 256,
                  scale_bytes: int = 2, itemsize: int = 4,
                  min_elems: int = 1024) -> dict:
    """Byte model for streaming weight publication
    (:mod:`horovod_tpu.serving`): a keyframe moves every leaf raw at
    ``itemsize``; a delta moves each quantizable leaf as blockwise int8
    (padded to whole blocks — the serving encoder quantizes the
    block-padded flat vector, so the pad bytes ARE on the wire) plus bf16
    scales, with sub-floor leaves riding their raw delta. Mirrors the live
    ``serving_publish_wire_bytes`` gauge exactly (model == gauge), and
    amortizes one keyframe per ``keyframe_every`` generations against the
    full-checkpoint bytes (``checkpoint.state_nbytes``) the handoff would
    otherwise pay per refresh."""
    shapes = _as_shapes(shapes)
    key = 0
    delta = 0
    for s in shapes:
        size = int(np.prod(s, dtype=np.int64))
        key += size * itemsize
        if size >= min_elems:
            padded = -(-size // block) * block
            delta += padded + (padded // block) * scale_bytes
        else:
            delta += size * itemsize
    amortized = (key + (keyframe_every - 1) * delta) / keyframe_every
    return {
        "keyframe_bytes": key,
        "delta_bytes": delta,
        "checkpoint_bytes": key,
        "amortized_bytes_per_generation": amortized,
        "delta_ratio_vs_checkpoint": delta / key if key else 0.0,
        "amortized_ratio_vs_checkpoint": amortized / key if key else 0.0,
        "keyframe_every": keyframe_every,
    }


def prefix_prefill_flops(prompt_lens, cached_lens, *, page_size: int,
                         prefill_chunk: int,
                         params_per_token: Optional[int] = None) -> dict:
    """Analytic prefill-savings model for the serving prefix cache
    (``tests/test_prefix_spec.py`` pins the live counters to it).

    Mirrors the engine's hit rules EXACTLY, so the measured
    ``serving_prefill_tokens`` delta on a deterministic workload pins to
    this model token-for-token:

    - a hit only aliases whole pages whose content chain is resident,
      up to ``cached_lens[i]`` shared-prefix tokens;
    - the hit rounds down to a multiple of
      ``lcm(page_size, prefill_chunk)`` — chunk starts must stay
      multiples of ``prefill_chunk`` or a clamped pad tail could fold
      into a real page;
    - the hit stays strictly below the prompt end: the final prompt
      token always prefills (it produces the first-token logits).

    ``prefill_token_ratio`` is cold/cached prefill tokens (≥ 1; the
    FLOP saving at ``2 · params · tokens`` per dense forward when
    `params_per_token` is given)."""
    lens = [int(x) for x in np.asarray(prompt_lens).reshape(-1)]
    shared = [int(x) for x in np.asarray(cached_lens).reshape(-1)]
    if len(lens) != len(shared):
        raise ValueError(
            f"prompt_lens and cached_lens length mismatch: "
            f"{len(lens)} vs {len(shared)}")
    ps, chunk = int(page_size), max(1, int(prefill_chunk))
    align = ps * chunk // math.gcd(ps, chunk)
    hits = []
    for l, c in zip(lens, shared):
        resident = min(c, l) // ps            # whole resident blocks
        cap = (l - 1) // align * (align // ps)  # aligned, < prompt end
        n = min(resident, cap)
        n -= n % (align // ps)
        hits.append(n * ps)
    cold = sum(lens)
    cached = sum(l - h for l, h in zip(lens, hits))
    out = {
        "cold_prefill_tokens": cold,
        "cached_prefill_tokens": cached,
        "saved_tokens": cold - cached,
        "hit_tokens_per_request": hits,
        "prefill_token_ratio": cold / cached if cached else float("inf"),
        "page_size": ps,
        "prefill_chunk": chunk,
        "alignment_tokens": align,
    }
    if params_per_token is not None:
        out["cold_prefill_flops"] = 2 * int(params_per_token) * cold
        out["cached_prefill_flops"] = 2 * int(params_per_token) * cached
    return out


def spec_decode_tokens(max_new: int, lookahead: int, *,
                       acceptance_rate: float = 1.0,
                       draft_cost: float = 0.0,
                       n_requests: int = 1) -> dict:
    """Analytic token-accounting model for speculative decoding
    (``tests/test_prefix_spec.py`` pins the live counters to it).

    The engine's schedule per request: the first token comes from the
    prefill forward; the remaining ``max_new − 1`` decode while the
    budget allows a full iteration — a speculative iteration needs
    ``K + 1`` tokens of headroom (K drafts + the verify's bonus token)
    and emits all ``K + 1`` under full acceptance, anything shorter
    falls back to one plain decode per token. At
    ``acceptance_rate == 1`` (a draft run at the target's full depth:
    draft argmax ≡ target argmax) the counts are exact integers the ``spec_proposed`` / ``spec_accepted``
    counters must match; for partial acceptance the expectation
    ``E[tokens/iteration] = sum_{i=0..K} α^i`` (per-token iid α) scales
    the decode-pass saving.

    ``decode_goodput_ratio`` is plain target passes over spec-mode
    target passes plus `draft_cost`-weighted draft passes (draft FLOPs
    as a fraction of a target pass, e.g. ``draft_depth / depth``)."""
    K = int(lookahead)
    if K < 1:
        raise ValueError(f"lookahead must be >= 1, got {lookahead}")
    a = float(acceptance_rate)
    decode = max(0, int(max_new) - 1)
    spec_iters = decode // (K + 1)
    plain = decode - spec_iters * (K + 1)
    R = int(n_requests)
    exp_per_iter = sum(a ** i for i in range(K + 1))
    out = {
        "max_new": int(max_new),
        "lookahead": K,
        "acceptance_rate": a,
        "spec_iterations": spec_iters * R,
        "plain_decodes": plain * R,
        "proposed": spec_iters * K * R,
        "accepted": int(spec_iters * K * R) if a >= 1.0
        else spec_iters * R * (exp_per_iter - 1.0),
        "expected_tokens_per_iteration": exp_per_iter,
        "target_passes_plain": decode * R,
        "target_passes_spec": (spec_iters + plain) * R,
        # K proposal forwards + 1 KV-backfill forward per iteration (the
        # engine writes d_K's draft KV so a fully-accepted round leaves
        # no hole behind the next frontier)
        "draft_passes": spec_iters * (K + 1) * R,
    }
    cost = ((spec_iters + plain)
            + float(draft_cost) * spec_iters * (K + 1))
    out["decode_goodput_ratio"] = decode / cost if cost else 1.0
    return out
