"""Decoder-only Transformer LM (flax) — the long-context benchmark workload.

No counterpart in the reference (its models are CNN benchmark harnesses,
``examples/tensorflow2_synthetic_benchmark.py``); this family exists to
exercise the TPU-native parallel axes the mesh layer provides beyond data
parallelism: sequence (ring/Ulysses attention over ``seq``), tensor (MLP and
attention projections sharded over ``model``), on top of DP.

TPU-tuned defaults: bfloat16 compute with float32 params, pre-LN blocks,
dimensions sized for MXU tiling (head_dim and mlp widths multiples of 128 at
benchmark scale). The attention implementation is injectable so the same
module runs dense attention under plain jit, flash attention single-chip, or
ring attention inside a ``shard_map`` over the ``seq`` axis.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def apply_rope(x, positions, *, base: float = 10000.0):
    """Rotary position embedding on ``[B, T, H, D]`` (D even), rotate-half
    (NeoX-style) convention: feature i pairs with feature i + D/2, rotated
    by ``positions * base**(-2i/D)``.

    Positions are the *global* token indices, so under sequence parallelism
    each shard rotates with its own offsets and ring/Ulysses attention sees
    correctly phased K — relative-position behavior is preserved across
    shard boundaries (the property that makes RoPE the long-context default
    over a learned absolute table)."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B?, T, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def default_attention(q, k, v, *, causal: bool = True, sm_scale=None):
    """Dense attention fallback (plain jit / tiny shapes). GQA-aware like
    the flash/ring implementations: K/V may carry fewer heads than Q."""
    if k.shape[2] != q.shape[2]:
        from horovod_tpu.ops.flash_attention import repeat_kv_heads

        k, v = repeat_kv_heads(q, k, v)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _decode_attention(q, k_cache, v_cache, start_pos):
    """Moved to :func:`horovod_tpu.ops.flash_attention.decode_attention`
    (the serving engine's paged variant shares the primitive); this alias
    keeps the historical name importable."""
    from horovod_tpu.ops.flash_attention import decode_attention

    return decode_attention(q, k_cache, v_cache, start_pos)


class TransformerBlock(nn.Module):
    dim: int
    heads: int
    mlp_ratio: int
    dtype: Any
    attention_fn: Callable
    kv_heads: Optional[int] = None  # GQA: fewer K/V heads (MQA = 1)
    use_rope: bool = False
    rope_base: float = 10000.0
    decode: bool = False
    cache_len: int = 0  # kv-cache capacity when decode=True
    # paged decode (the serving engine): the cache is a shared page pool
    # [num_pages, page_size, H_kv, D] addressed through a per-row page
    # table instead of one contiguous [B, cache_len, ...] buffer
    paged: bool = False
    page_size: int = 0
    num_pages: int = 0

    @nn.compact
    def __call__(self, x, positions=None, page_table=None):
        head_dim = self.dim // self.heads
        h_kv = self.kv_heads or self.heads
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        if h_kv == self.heads:
            qkv = nn.Dense(3 * self.dim, use_bias=False, dtype=self.dtype,
                           name="qkv")(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            # GQA: smaller K/V projections — parameter AND kv-cache savings
            # flow straight through to the attention stack (the ring/zigzag
            # ppermute bundles and the Pallas kv buffers stay H_kv-wide)
            q = nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                         name="q_proj")(h)
            kv = nn.Dense(2 * h_kv * head_dim, use_bias=False,
                          dtype=self.dtype, name="kv_proj")(h)
            k, v = jnp.split(kv, 2, axis=-1)
        split_q = lambda t: t.reshape(*t.shape[:2], self.heads, head_dim)
        split_kv = lambda t: t.reshape(*t.shape[:2], h_kv, head_dim)
        q, k, v = split_q(q), split_kv(k), split_kv(v)
        if self.use_rope:
            if positions is None:
                # a silent local-arange fallback would be wrong under SP
                # (every shard would phase from 0); demand global offsets
                raise ValueError(
                    "use_rope=True requires positions (global token "
                    "indices) — TransformerLM passes them automatically"
                )
            q = apply_rope(q, positions, base=self.rope_base)
            k = apply_rope(k, positions, base=self.rope_base)
        if self.decode and self.paged:
            from horovod_tpu.ops.flash_attention import (
                paged_decode_attention,
            )

            if page_table is None:
                raise ValueError(
                    "paged decode requires a page_table ([B, pages_per_"
                    "seq] int32) — the serving engine passes it")
            # page pool [P, page_size, H_kv, D]: token at global position
            # p of row b lives in page page_table[b, p // page_size] at
            # offset p % page_size. Writes scatter the chunk's T tokens
            # into their flat pool slots; the engine routes masked rows /
            # pad tail positions to a reserved trash page (page 0), whose
            # contents are never causally visible.
            cache_k = self.variable(
                "cache", "k_pages", jnp.zeros,
                (self.num_pages, self.page_size, h_kv, head_dim),
                self.dtype)
            cache_v = self.variable(
                "cache", "v_pages", jnp.zeros,
                (self.num_pages, self.page_size, h_kv, head_dim),
                self.dtype)
            page_idx = positions // self.page_size          # [B, T]
            offset = positions % self.page_size
            # out-of-range page_idx clamps under jit (take_along_axis),
            # matching the engine's contract that over-capacity positions
            # only ever carry masked pad tokens
            page_ids = jnp.take_along_axis(
                page_table, jnp.minimum(
                    page_idx, page_table.shape[1] - 1), axis=1)
            slots = (page_ids * self.page_size + offset).reshape(-1)
            flat_shape = (self.num_pages * self.page_size, h_kv, head_dim)
            kf = cache_k.value.reshape(flat_shape).at[slots].set(
                k.astype(self.dtype).reshape(-1, h_kv, head_dim))
            vf = cache_v.value.reshape(flat_shape).at[slots].set(
                v.astype(self.dtype).reshape(-1, h_kv, head_dim))
            cache_k.value = kf.reshape(cache_k.value.shape)
            cache_v.value = vf.reshape(cache_v.value.shape)
            start = positions[:, 0]  # [B], per-row frontier
            att = paged_decode_attention(
                q, cache_k.value, cache_v.value, page_table, start,
                page_size=self.page_size)
        elif self.decode:
            # chunk of T tokens in, kv cache [B, cache_len, H_kv, D] updated
            # in place at each row's start position (GQA: H_kv-wide — the
            # cache memory saving). T = prompt length on prefill, 1 after.
            b = x.shape[0]
            cache_k = self.variable(
                "cache", "k", jnp.zeros,
                (b, self.cache_len, h_kv, head_dim), self.dtype)
            cache_v = self.variable(
                "cache", "v", jnp.zeros,
                (b, self.cache_len, h_kv, head_dim), self.dtype)
            start = positions[:, 0]  # [B], per-row write offset
            upd = jax.vmap(
                lambda c, kv, p: jax.lax.dynamic_update_slice(
                    c, kv, (p, 0, 0))
            )
            cache_k.value = upd(cache_k.value, k.astype(self.dtype), start)
            cache_v.value = upd(cache_v.value, v.astype(self.dtype), start)
            att = _decode_attention(q, cache_k.value, cache_v.value, start)
        else:
            att = self.attention_fn(q, k, v, causal=True)
        att = att.reshape(*att.shape[:2], self.dim)
        x = x + nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                         name="proj")(att)

        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        h = nn.Dense(self.mlp_ratio * self.dim, dtype=self.dtype,
                     name="mlp_up")(h)
        h = nn.gelu(h)
        h = nn.Dense(self.dim, dtype=self.dtype, name="mlp_down")(h)
        return x + h


class TransformerLM(nn.Module):
    """Causal LM. Input: int tokens [B, T] (a *local* sequence shard when run
    under sequence parallelism — pass ``positions`` with the global offsets so
    position embeddings line up). Output: logits [B, T, vocab] — in
    ``dtype``, as the head computed them, from a training-shape call: the
    loss does its arithmetic in float32 (``training.token_xent`` upcasts
    inside its own fusions); float32 from a kv-cache call (``decode=True``:
    ``generate()``, the serving engine)."""

    vocab: int = 32000
    dim: int = 512
    depth: int = 8
    heads: int = 8
    kv_heads: Optional[int] = None  # GQA (heads % kv_heads == 0); MQA = 1
    mlp_ratio: int = 4
    max_len: int = 65536
    dtype: Any = jnp.bfloat16
    attention_fn: Callable = default_attention
    pos_embedding: str = "learned"  # "learned" table or "rope" (rotary)
    rope_base: float = 10000.0
    decode: bool = False  # chunked/single-token steps against a kv cache
    cache_len: Optional[int] = None  # kv-cache capacity (default: max_len)
    paged: bool = False  # page-pool kv cache (serving engine)
    page_size: int = 0
    num_pages: int = 0

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = True,
                 page_table=None):
        if self.pos_embedding not in ("learned", "rope"):
            raise ValueError(
                f"pos_embedding must be 'learned' or 'rope', "
                f"got {self.pos_embedding!r}"
            )
        if self.pos_embedding == "rope" and (self.dim // self.heads) % 2:
            raise ValueError(
                f"rope needs an even head_dim, got "
                f"{self.dim // self.heads} (dim={self.dim}, "
                f"heads={self.heads})"
            )
        if self.decode and positions is None:
            raise ValueError(
                "decode=True requires positions (the current cache index "
                "as a [B, 1] array) — use generate()"
            )
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        x = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                     name="tok_embed")(tokens)
        use_rope = self.pos_embedding == "rope"
        if not use_rope:
            pos_table = self.param(
                "pos_embed",
                nn.initializers.normal(0.02),
                (self.max_len, self.dim),
            )
            # jnp.take clamps out-of-range indices under jit: a paged
            # prefill chunk's masked pad tail may carry positions past the
            # table — those rows' logits are never consumed
            x = x + jnp.take(pos_table, positions, axis=0).astype(self.dtype)
        for i in range(self.depth):
            x = TransformerBlock(
                self.dim, self.heads, self.mlp_ratio, self.dtype,
                self.attention_fn, kv_heads=self.kv_heads,
                use_rope=use_rope, rope_base=self.rope_base,
                decode=self.decode,
                cache_len=self.cache_len or self.max_len,
                paged=self.paged, page_size=self.page_size,
                num_pages=self.num_pages,
                name=f"block{i}",
            )(x, positions=positions if (use_rope or self.decode) else None,
              page_table=page_table)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        logits = nn.Dense(self.vocab, use_bias=False, dtype=self.dtype,
                          name="lm_head")(x)
        # a kv-cache step's few rows go to a sampler or to numpy: float32.
        # A training call's [B, T, vocab] stay as the head computed them,
        # for the loss to upcast inside its own fusions (token_xent): an
        # upcast here is a second, float32 copy of them in HBM
        return logits.astype(jnp.float32) if self.decode else logits


def TransformerTiny(**kw):
    kw.setdefault("vocab", 1024)
    kw.setdefault("dim", 64)
    kw.setdefault("depth", 2)
    kw.setdefault("heads", 4)
    kw.setdefault("max_len", 4096)
    return TransformerLM(**kw)


def TransformerSmall(**kw):
    """~GPT-2-small scale; dims are MXU-tile multiples."""
    kw.setdefault("vocab", 32768)
    kw.setdefault("dim", 768)
    kw.setdefault("depth", 12)
    kw.setdefault("heads", 12)
    return TransformerLM(**kw)


def transformer_param_specs(params, model_axis: str = "model"):
    """Tensor-parallel PartitionSpecs for a TransformerLM param tree
    (Megatron-style: qkv/up-proj sharded on the output dim, proj/down-proj on
    the input dim, so each block needs exactly one psum — which XLA inserts
    from these annotations; embeddings/vocab sharded on the feature axis)."""
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        name = "/".join(names)
        if leaf.ndim < 2:
            return P()
        if ("qkv" in name or "mlp_up" in name or "q_proj" in name
                or "kv_proj" in name):
            return P(None, model_axis)
        if "proj" in name or "mlp_down" in name:
            return P(model_axis, None)
        if "lm_head" in name:
            return P(None, model_axis)
        if "tok_embed" in name or "pos_embed" in name:
            return P(None, model_axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _tp_layernorm(x, scale, bias, *, eps: float = 1e-6):
    # flax.linen.LayerNorm's stats formula (mean-of-squares minus squared
    # mean, clamped) so tp_block_apply is numerically interchangeable with
    # TransformerBlock.apply
    mu = jnp.mean(x, axis=-1, keepdims=True)
    mu2 = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    var = jnp.maximum(0.0, mu2 - jnp.square(mu))
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def tp_block_apply(block_params, x, *, heads: int, axis: str = "tp"):
    """One transformer block, tensor-parallel over a bound mesh axis.

    The explicit (shard_map) counterpart of the GSPMD annotations from
    :func:`transformer_param_specs` — which remains the production TP
    path; this function exists so the one-psum-per-matmul-pair schedule
    is stated in code rather than inferred by the partitioner, and so
    tests can pin the two against each other. Call it *inside* a
    shard_map region over ``axis`` with the full (replicated) param dict
    of a single :class:`TransformerBlock`; each rank slices its own
    column/row blocks (Megatron-style: qkv and mlp_up column-split,
    proj and mlp_down row-split) so the block costs exactly two psums —
    one after the attention projection, one after mlp_down.

    Restrictions: full multi-head attention only (``kv_heads`` unset or
    equal to ``heads`` — the params must carry a fused ``qkv`` kernel),
    no RoPE, no kv-cache (training/prefill layout, ``decode=False``).
    ``heads`` and the mlp hidden width must be divisible by the axis
    size.
    """
    from horovod_tpu.ops.collective import _axis_size

    if "qkv" not in block_params:
        raise ValueError(
            "tp_block_apply requires a fused qkv kernel (kv_heads unset "
            "or == heads); GQA blocks need the GSPMD path "
            "(transformer_param_specs)")
    n = _axis_size(axis)
    r = jax.lax.axis_index(axis)
    dim = x.shape[-1]
    if heads % n:
        raise ValueError(f"heads={heads} not divisible by tp axis size {n}")
    w = dim // n  # per-rank head-block width (heads//n heads, contiguous)
    head_dim = dim // heads

    def cols(kernel, off, width):
        return jax.lax.dynamic_slice_in_dim(kernel, off, width, axis=1)

    def rows(kernel, off, width):
        return jax.lax.dynamic_slice_in_dim(kernel, off, width, axis=0)

    h = _tp_layernorm(x, block_params["ln1"]["scale"],
                      block_params["ln1"]["bias"])
    # fused qkv kernel layout is [D, 3D] = [q | k | v]; this rank takes
    # the same column window r*w inside each third
    qkv_k = block_params["qkv"]["kernel"]
    qkv_local = jnp.concatenate(
        [cols(qkv_k, base + r * w, w) for base in (0, dim, 2 * dim)],
        axis=1)
    qkv = h @ qkv_local                                     # [B, T, 3w]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda t: t.reshape(*t.shape[:2], heads // n, head_dim)
    att = default_attention(split(q), split(k), split(v), causal=True)
    att = att.reshape(*att.shape[:2], w)
    # proj row-split: each rank contributes its head-block's slice of the
    # contraction; psum #1 completes it
    partial = att @ rows(block_params["proj"]["kernel"], r * w, w)
    x = x + jax.lax.psum(partial, axis)

    h = _tp_layernorm(x, block_params["ln2"]["scale"],
                      block_params["ln2"]["bias"])
    up_k = block_params["mlp_up"]["kernel"]
    hidden = up_k.shape[1]
    if hidden % n:
        raise ValueError(
            f"mlp hidden width {hidden} not divisible by tp axis size {n}")
    fw = hidden // n
    # mlp_up bias is column-split with its kernel: it must land before the
    # gelu nonlinearity, so it cannot wait for the psum
    h = h @ cols(up_k, r * fw, fw) + jax.lax.dynamic_slice_in_dim(
        block_params["mlp_up"]["bias"], r * fw, fw, axis=0)
    h = nn.gelu(h)
    partial = h @ rows(block_params["mlp_down"]["kernel"], r * fw, fw)
    # mlp_down bias is replicated and must be added exactly once — after
    # psum #2, not inside the summed partials
    return x + jax.lax.psum(partial, axis) + block_params["mlp_down"]["bias"]


def generate(model: TransformerLM, params, prompt, *, max_new_tokens: int,
             temperature: float = 0.0, rng=None, prompt_lens=None):
    """Autoregressive decoding with a KV cache (the inference path;
    reference ``docs/inference.rst`` covers only checkpoint handling — the
    reference has no model code to decode with).

    One batched prefill forward writes the whole prompt's K/V into the
    cache, then a ``lax.scan`` decodes one token per step — greedy
    (``temperature=0``) or categorical sampling. The cache is sized to
    ``T_prompt + max_new_tokens`` (not ``max_len``) and holds ``H_kv``-wide
    K/V per block (GQA's memory saving) — static shapes throughout, the
    standard TPU decode loop.

    Ragged batches: pass ``prompt_lens`` ``[B]`` with RIGHT-padded
    ``prompt`` (pad values are arbitrary) and every row decodes from its
    own length — per-row cache offsets/causal masks make the pad slots
    unreachable until a real decode step overwrites them, so no attention
    masking of pads is needed.

    Args:
      model: a ``TransformerLM`` (its ``decode``/``cache_len`` are
        overridden).
      params: trained parameter tree.
      prompt: int tokens ``[B, T_prompt]`` (right-padded when ragged).
      max_new_tokens: tokens to append (per row).
      temperature: 0 = greedy argmax; > 0 = sample logits/temperature.
      rng: PRNGKey, required when ``temperature > 0``.
      prompt_lens: optional ``[B]`` true prompt lengths (1..T_prompt).

    Returns:
      int tokens ``[B, T_prompt + max_new_tokens]``; ragged rows carry
      their generated tokens at ``[L_i, L_i + max_new_tokens)`` — columns
      beyond that are unspecified padding.
    """
    import dataclasses

    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 needs an rng key")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    b, t_prompt = prompt.shape
    total = t_prompt + max_new_tokens
    if total > model.max_len:
        raise ValueError(
            f"prompt + max_new_tokens = {total} exceeds max_len "
            f"{model.max_len}"
        )
    prompt = jnp.asarray(prompt, jnp.int32)
    ragged = prompt_lens is not None
    if ragged:
        lens = jnp.asarray(prompt_lens, jnp.int32)
        if lens.shape != (b,):
            raise ValueError(f"prompt_lens must be [B]={b}, got {lens.shape}")
        if not isinstance(lens, jax.core.Tracer):
            lo, hi = int(lens.min()), int(lens.max())
            if lo < 1 or hi > t_prompt:
                raise ValueError(
                    f"prompt_lens must be in [1, {t_prompt}], got "
                    f"[{lo}, {hi}]"
                )
    else:
        lens = jnp.full((b,), t_prompt, jnp.int32)
    dec = dataclasses.replace(model, decode=True, cache_len=total, name=None)
    base_rng = rng if rng is not None else jax.random.PRNGKey(0)

    def sample(logits, i):
        if temperature > 0.0:
            return jax.random.categorical(
                jax.random.fold_in(base_rng, i),
                logits / temperature, axis=-1,
            ).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # zero cache from shapes only — no throwaway parameter init
    prefill_pos = jnp.broadcast_to(
        jnp.arange(t_prompt, dtype=jnp.int32)[None, :], (b, t_prompt))
    shapes = jax.eval_shape(
        dec.init, jax.random.PRNGKey(0), prompt, positions=prefill_pos
    )["cache"]
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    # prefill: one forward over the (padded) prompt fills the cache; pad
    # K/V beyond a row's length stays masked until decode overwrites it
    logits, mut = dec.apply(
        {"params": params, "cache": cache}, prompt,
        positions=prefill_pos, mutable=["cache"],
    )
    # each row's first sampled token comes from ITS last real position
    last_logits = jnp.take_along_axis(
        logits, (lens - 1)[:, None, None], axis=1)[:, 0]
    # rng fold indices: prefill samples at 0, decode step i at i+1 —
    # disjoint by construction, so no two draws share a folded key
    first = sample(last_logits, 0)

    def step(carry, i):
        cache, tok = carry
        pos = (lens + i)[:, None]  # [B, 1], per-row decode position
        logits, mut = dec.apply(
            {"params": params, "cache": cache}, tok[:, None],
            positions=pos, mutable=["cache"],
        )
        nxt = sample(logits[:, -1], i + 1)
        return (mut["cache"], nxt), nxt

    (_, _), ys = jax.lax.scan(
        step, (mut["cache"], first),
        jnp.arange(max_new_tokens - 1, dtype=jnp.int32),
    )
    gen = jnp.concatenate([first[:, None], ys.T], axis=1)

    out = jnp.pad(prompt, ((0, 0), (0, max_new_tokens)))
    # place each row's generated run at its own offset
    return jax.vmap(
        lambda row, g, l: jax.lax.dynamic_update_slice(row, g, (l,))
    )(out, gen, lens)
