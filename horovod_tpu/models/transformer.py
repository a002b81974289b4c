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

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN scaling of a layer's rotary frequencies (Peng et al. 2023, as
    the published ``rope_type: yarn`` code computes it): frequency j is
    blended between ``base**(-2j/D)`` and that over ``factor`` by a linear
    ramp between the correction dims of ``beta_fast`` and ``beta_slow``
    turns over ``original_max_len`` positions; cos and sin are scaled by
    ``attention_factor``."""

    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def blend(self, freqs, head_dim: int, base: float):
        def correction_dim(turns):
            return head_dim * math.log(self.original_max_len / (
                turns * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), head_dim - 1)
        ramp = np.clip((np.arange(head_dim // 2) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        return freqs / self.factor * ramp + freqs * (1.0 - ramp)


@dataclasses.dataclass(frozen=True)
class SwiGLU:
    """A bias-free SwiGLU MLP in place of a block's GELU MLP:
    ``(silu(h W_gate) * (h W_up)) W_down``, ``width`` wide."""

    width: int


@dataclasses.dataclass(frozen=True)
class Experts:
    """A routed-expert FFN (:func:`horovod_tpu.parallel.moe.routed_experts`)
    in place of a block's MLP: a float32 router ``routed`` wide, ``top_k``
    experts a token, experts ``width`` wide; of them this model holds
    ``count`` from ``first`` (default: all) and computes their part of the
    layer. ``select`` (``probabilities [tokens, routed] -> scores``) chooses
    a token's experts in the router's place, by the ``top_k`` of its
    scores; the weights stay the router's. ``scale`` multiplies the routed
    sum (a model's routed scaling factor). ``shared`` is the
    width of an expert every token passes through, added to the
    routed sum unweighted, or with ``shared_gate`` times ``sigmoid(h
    w_sg)``, ``w_sg`` ``[dim, 1]``: it is computed whole wherever the layer
    is, so across the holders of a layer's experts it counts once.

    ``activation`` is every expert's, the shared one's too: ``"swiglu"``,
    ``(silu(h W_gate) * (h W_up)) W_down``, or ``"relu2"``, ``relu(h
    W_up)^2 W_down`` (no gate matrix). ``router`` ``"softmax"`` weighs a
    token's chosen experts by their probabilities over their sum;
    ``"sigmoid"`` chooses by ``top_k(s + b)`` of ``s = sigmoid(h W_r)``,
    ``b`` a selection bias that no gradient trains (the block's
    ``batch_stats`` ``router_bias``, zeros where it holds none), and weighs
    by ``s`` over the chosen ``s``' sum. With ``latent`` the routed experts
    work in a space of that width: ``fc1_latent_proj`` takes ``h`` down to
    it, the routed sum comes back through ``fc2_latent_proj`` (both under
    ``hvd.moe_latent``); the router and the shared expert read ``h``
    itself."""

    routed: int
    top_k: int
    width: int
    first: int = 0
    count: Optional[int] = None
    select: Optional[Callable] = None
    scale: float = 1.0
    shared: Optional[int] = None
    shared_gate: bool = False
    activation: str = "swiglu"
    router: str = "softmax"
    latent: Optional[int] = None

    def __post_init__(self):
        if self.activation not in ("swiglu", "relu2"):
            raise ValueError("activation must be 'swiglu' or 'relu2', got "
                             f"{self.activation!r}")
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError("router must be 'softmax' or 'sigmoid', got "
                             f"{self.router!r}")


@dataclasses.dataclass(frozen=True)
class GatedDelta:
    """A Gated DeltaNet token mixer (:mod:`horovod_tpu.ops.gated_delta`) in
    place of a block's attention: ``key_heads`` heads of ``key_dim`` for q
    and k, each serving ``value_heads / key_heads`` value heads of
    ``value_dim``, a causal depthwise convolution of ``conv`` taps over
    ``[q | k | v]``; the projections laid out as the published checkpoints
    lay them (``in_proj_qkvz``, ``in_proj_ba``, ``out_proj``)."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv: int = 4


@dataclasses.dataclass(frozen=True)
class Mamba2:
    """A Mamba-2 token mixer (:mod:`horovod_tpu.ops.mamba2`) in place of a
    block's attention: ``heads`` heads of ``head_dim`` channels, their
    ``B`` and ``C`` in ``groups`` groups of ``state`` (head ``h`` reads group
    ``h // (heads / groups)``), a causal depthwise convolution of ``conv``
    taps with a bias over ``[x | B | C]``, the recurrence in chunks of
    ``chunk`` tokens; the projections laid out as the published checkpoints
    lay them (``in_proj`` ``[z | x | B | C | dt]``, ``out_proj``)."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int = 4
    chunk: int = 128


@dataclasses.dataclass(frozen=True)
class Layer:
    """One block of a :class:`TransformerLM` built from a per-layer
    description (``TransformerLM(layers=...)``): its attention (``heads``
    query heads on ``kv_heads`` K/V heads of ``head_dim``, separate
    bias-free q/k/v projections; with ``qk_norm`` q and k each normalised
    over the head by the model's ``norm``; rotary with ``rope_base`` and
    optional ``yarn`` over the first ``rotary_dim`` features of a head,
    default all; causal, within ``window`` positions where set; a ``gate``
    of arXiv:2505.06708 on the attention's output before the output
    projection: ``"head"`` each head's output times
    ``sigmoid(h W_g)``, ``W_g`` ``[dim, heads]``; ``"element"`` each
    feature times the sigmoid of a second half of the head's query
    projection, ``[q | gate]`` a head) or, where ``mixer`` is given, a
    :class:`GatedDelta` or :class:`Mamba2` in its place; and its FFN (a
    GELU MLP ``mlp_ratio`` x dim wide, a :class:`SwiGLU` MLP, or
    :class:`Experts`).

    A block may be one part alone, ``x + part(norm(x))`` (Nemotron-H's
    blocks): ``ffn=None`` is its mixer alone, no heads and no mixer its FFN
    alone."""

    heads: int = 0
    head_dim: int = 0
    kv_heads: Optional[int] = None
    rope_base: float = 10000.0
    yarn: Optional[Yarn] = None
    window: Optional[int] = None
    ffn: Union[int, SwiGLU, Experts, None] = 4
    rotary_dim: Optional[int] = None
    gate: Optional[str] = None
    qk_norm: bool = False
    mixer: Union[GatedDelta, Mamba2, None] = None

    def __post_init__(self):
        # a configuration's boolean key (Laguna's ``gating``) passes
        # ``True`` / ``False``: the head-wise gate, or none
        if isinstance(self.gate, bool):
            object.__setattr__(self, "gate", "head" if self.gate else None)
        if self.gate not in _GATES:
            raise ValueError(
                f"gate must be one of {_GATES}, got {self.gate!r}")


_GATES = (None, "head", "element")

#: a norm of the ``1 + w`` form (``w`` zeros at start):
#: ``x rsqrt(mean(x^2) + eps) (1 + w)``
ZERO_CENTRED = "zero_centred_rmsnorm"


def refuse_training_only(model, what: str):
    """Raise where ``what`` (an entry point other than training) is handed
    a :class:`TransformerLM` with parts only the training path computes."""
    layers = getattr(model, "layers", None) or ()
    found = [part for i, layer in enumerate(layers)
             for part in _training_only(
                 f"block{i}", layer.mixer, layer.qk_norm, layer.gate,
                 layer.ffn, heads=layer.heads, has_ffn=layer.ffn is not None)]
    if getattr(model, "pos_embedding", None) == "none":
        found = ["pos_embedding='none'"] + found
    _refuse_forms(what, getattr(model, "norm", None), found)


def _training_only(name, mixer, qk_norm, gate, ffn, *, heads=1,
                   has_ffn=True):
    """A block's parts that only the training path computes, named: a
    :class:`GatedDelta` or :class:`Mamba2` mixer, q/k norms, the
    element-wise gate, a gated shared expert, experts of the ``relu2``
    activation, the ``sigmoid`` router or a ``latent`` width, a block of
    one part."""
    found = []
    if isinstance(mixer, Mamba2):
        found.append(f"{name}: the Mamba-2 layer {mixer}")
    elif mixer is not None:
        found.append(f"{name}: the gated-delta layer {mixer}")
    if qk_norm:
        found.append(f"{name}: qk_norm=True")
    if gate == "element":
        found.append(f"{name}: gate='element'")
    if isinstance(ffn, Experts):
        found += [f"{name}: Experts({field})" for field, on in (
            ("shared_gate=True", ffn.shared_gate),
            ("activation='relu2'", ffn.activation == "relu2"),
            ("router='sigmoid'", ffn.router == "sigmoid"),
            (f"latent={ffn.latent}", ffn.latent is not None)) if on]
    if mixer is None and not heads:
        found.append(f"{name}: no heads and no mixer, a block of its FFN "
                     "alone")
    if not has_ffn:
        found.append(f"{name}: ffn=None, a block of its mixer alone")
    return found


def _refuse_forms(what, norm, found):
    """Raise, naming each, where ``found`` (:func:`_training_only`'s parts)
    is not empty or ``norm`` is the zero-centred one."""
    if norm == ZERO_CENTRED:
        found = [f"norm={ZERO_CENTRED!r}"] + found
    if found:
        raise ValueError(
            f"{what} has no path for {'; '.join(found)}: a gated-delta or "
            "Mamba-2 layer keeps a recurrent state, not K/V, and these forms "
            "are computed by TransformerLM's training-shape call only")


def apply_rope(x, positions, *, base: float = 10000.0,
               yarn: Optional[Yarn] = None,
               rotary_dim: Optional[int] = None):
    """Rotary position embedding on ``[B, T, H, D]`` (D even), rotate-half
    (NeoX-style) convention: feature i pairs with feature i + D/2, rotated
    by ``positions * base**(-2i/D)`` (frequencies and amplitude rescaled
    where ``yarn`` is set). With ``rotary_dim`` (even, under D) only the
    first ``rotary_dim`` features are rotated, as a head of that size would
    be (``yarn``'s correction dims reckoned over it too), and the rest
    pass through.

    Positions are the *global* token indices, so under sequence parallelism
    each shard rotates with its own offsets and ring/Ulysses attention sees
    correctly phased K — relative-position behavior is preserved across
    shard boundaries (the property that makes RoPE the long-context default
    over a learned absolute table)."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        if rotary_dim % 2 or not 0 < rotary_dim < x.shape[-1]:
            raise ValueError(
                f"rotary_dim must be even and at most the head's "
                f"{x.shape[-1]} features, got {rotary_dim}")
        return jnp.concatenate(
            [apply_rope(x[..., :rotary_dim], positions, base=base, yarn=yarn),
             x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn.blend(
            float(base) ** (-np.arange(half) / half), d, base), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B?, T, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def default_attention(q, k, v, *, causal: bool = True, sm_scale=None,
                      window: Optional[int] = None):
    """Dense attention fallback (plain jit / tiny shapes). GQA-aware like
    the flash/ring implementations: K/V may carry fewer heads than Q.
    ``window`` (causal only): row i sees column j where ``i - j < window``."""
    if k.shape[2] != q.shape[2]:
        from horovod_tpu.ops.flash_attention import repeat_kv_heads

        k, v = repeat_kv_heads(q, k, v)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((t_q, t_k), bool), -window)
        s = jnp.where(mask[None, None], s, -1e30)
    elif window is not None:
        raise ValueError("window needs causal=True")
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
    # a block from a per-layer description (:class:`Layer`): a head size of
    # its own (separate q/k/v projections), YaRN, a window, rotary over part
    # of a head, a head-wise output gate, a SwiGLU MLP or routed experts
    head_dim: Optional[int] = None
    yarn: Optional[Yarn] = None
    window: Optional[int] = None
    experts: Optional[Experts] = None
    rotary_dim: Optional[int] = None
    gate: Optional[str] = None  # Layer's: "head" or "element"
    swiglu: Optional[SwiGLU] = None
    norm: str = "layernorm"  # or "rmsnorm", or ZERO_CENTRED
    norm_eps: float = 1e-6
    qk_norm: bool = False
    mixer: Union[GatedDelta, Mamba2, None] = None
    # a block of its mixer alone (Layer's ``ffn=None``); one of its FFN
    # alone has no heads and no mixer
    has_ffn: bool = True

    def _norm(self, name):
        return make_norm(self.norm, self.norm_eps, self.dtype, name)

    @nn.compact
    def __call__(self, x, positions=None, page_table=None):
        if self.decode:
            _refuse_forms("kv-cache decoding", self.norm, _training_only(
                self.name, self.mixer, self.qk_norm, self.gate, self.experts,
                heads=self.heads, has_ffn=self.has_ffn))
        if self.mixer is None and not self.heads:
            return x + self._ffn(self._norm("ln1")(x))
        if self.mixer is not None:
            mix = (self._mamba2 if isinstance(self.mixer, Mamba2)
                   else self._gated_delta)
            x = x + mix(self._norm("ln1")(x))
            if not self.has_ffn:
                return x
            return x + self._ffn(self._norm("ln2")(x))
        if self.decode and (self.window is not None
                            or self.experts is not None
                            or self.rotary_dim is not None or self.gate
                            or self.swiglu is not None):
            raise NotImplementedError(
                "kv-cache decoding (generate(), the serving engine) handles "
                "full causal attention with whole-head rotary and GELU MLP "
                f"blocks only: this block has window={self.window}, "
                f"experts={self.experts}, rotary_dim={self.rotary_dim}, "
                f"gate={self.gate}, ffn={self.swiglu}")
        head_dim = self.head_dim or self.dim // self.heads
        h_kv = self.kv_heads or self.heads
        h = self._norm("ln1")(x)
        if self.head_dim is not None:
            q, k, v = (
                nn.Dense(n * head_dim, use_bias=False, dtype=self.dtype,
                         name=name)(h)
                for name, n in (("q_proj", self.heads * (
                    2 if self.gate == "element" else 1)), ("k_proj", h_kv),
                                ("v_proj", h_kv)))
        elif h_kv == self.heads:
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
        if self.gate == "element":
            # a head's projection is its query, then its gate
            q, gate = jnp.split(q.reshape(*q.shape[:2], self.heads,
                                          2 * head_dim), 2, axis=-1)
        q, k, v = split_q(q), split_kv(k), split_kv(v)
        if self.qk_norm:
            q, k = self._norm("q_norm")(q), self._norm("k_norm")(k)
        if self.use_rope:
            if positions is None:
                # a silent local-arange fallback would be wrong under SP
                # (every shard would phase from 0); demand global offsets
                raise ValueError(
                    "use_rope=True requires positions (global token "
                    "indices) — TransformerLM passes them automatically"
                )
            rope = dict(base=self.rope_base, yarn=self.yarn,
                        rotary_dim=self.rotary_dim)
            q = apply_rope(q, positions, **rope)
            k = apply_rope(k, positions, **rope)
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
        elif self.window is not None:
            att = self.attention_fn(q, k, v, causal=True, window=self.window)
        else:
            att = self.attention_fn(q, k, v, causal=True)
        if self.gate == "element":
            att = att * jax.nn.sigmoid(gate).astype(att.dtype)
        elif self.gate == "head":
            # one sigmoid a head from the block's normalised input
            g = nn.Dense(self.heads, use_bias=False, dtype=self.dtype,
                         name="gate_proj")(h)
            att = att * jax.nn.sigmoid(g)[..., None].astype(att.dtype)
        att = att.reshape(*att.shape[:2], self.heads * head_dim)
        x = x + nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                         name="proj")(att)
        if not self.has_ffn:
            return x
        return x + self._ffn(self._norm("ln2")(x))

    def _ffn(self, h):
        """What the block's MLP, SwiGLU MLP or routed experts add."""
        if self.experts is not None:
            return self._routed(h)
        if self.swiglu is not None:
            return self._swiglu(h, self.swiglu.width, "mlp")
        h = nn.Dense(self.mlp_ratio * self.dim, dtype=self.dtype,
                     name="mlp_up")(h)
        h = nn.gelu(h)
        return nn.Dense(self.dim, dtype=self.dtype, name="mlp_down")(h)

    def _gated_delta(self, h):
        """What the block's :class:`GatedDelta` mixer adds: the in- and
        out-projections in ``dtype`` around
        :func:`~horovod_tpu.ops.gated_delta.gated_delta_mixer` (float32,
        under ``hvd.gdn``). Parameters as the published checkpoints name
        them; ``conv1d`` ``[channels, taps]`` at normal(0.02), as the
        published code draws every convolution, ``A_log`` ``log U(0, 16)``,
        ``dt_bias`` and the output norm's ``norm_scale`` ones."""
        from horovod_tpu.ops.gated_delta import gated_delta_mixer

        m = self.mixer
        r = m.value_heads // m.key_heads
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        qkvz = dense(m.key_heads * (2 * m.key_dim + 2 * r * m.value_dim),
                     name="in_proj_qkvz")(h)
        ba = dense(2 * m.value_heads, name="in_proj_ba")(h)
        channels = 2 * m.key_heads * m.key_dim + m.value_heads * m.value_dim
        conv = self.param("conv1d", nn.initializers.normal(0.02),
                          (channels, m.conv))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, jnp.float32, 0.0, 16.0)), (m.value_heads,))
        dt_bias = self.param("dt_bias", nn.initializers.ones,
                             (m.value_heads,))
        norm = self.param("norm_scale", nn.initializers.ones, (m.value_dim,))
        with jax.named_scope("hvd.gdn"):
            y = gated_delta_mixer(
                qkvz, ba, conv, a_log, dt_bias, norm, key_heads=m.key_heads,
                key_dim=m.key_dim, value_dim=m.value_dim, eps=self.norm_eps)
        return dense(self.dim, name="out_proj")(y)

    def _mamba2(self, h):
        """What the block's :class:`Mamba2` mixer adds: the in- and
        out-projections in ``dtype`` around
        :func:`~horovod_tpu.ops.mamba2.mamba2_mixer` (float32, under
        ``hvd.ssm``). Parameters as the published checkpoints name them;
        ``conv1d`` ``[channels, taps]`` and ``conv1d_bias`` as torch draws a
        ``Conv1d``'s (uniform within ``1 / sqrt(taps)``), ``A_log`` ``log
        U(1, 16)``, ``D`` and the gated norm's ``norm_scale`` ones,
        ``dt_bias`` the inverse softplus of a step size log-uniform in
        ``[0.001, 0.1]``, at least ``1e-4`` (the published code's)."""
        from horovod_tpu.ops.mamba2 import mamba2_mixer

        m = self.mixer
        inner, bc = m.heads * m.head_dim, 2 * m.groups * m.state
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        zxbcdt = dense(2 * inner + bc + m.heads, name="in_proj")(h)
        bound = m.conv ** -0.5
        conv_init = functools.partial(jax.random.uniform, minval=-bound,
                                      maxval=bound)
        conv = self.param("conv1d", conv_init, (inner + bc, m.conv))
        conv_bias = self.param("conv1d_bias", conv_init, (inner + bc,))

        def dt_init(key, shape):
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(0.1))),
                1e-4)
            return dt + jnp.log(-jnp.expm1(-dt))

        dt_bias = self.param("dt_bias", dt_init, (m.heads,))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, jnp.float32, 1.0, 16.0)), (m.heads,))
        d = self.param("D", nn.initializers.ones, (m.heads,))
        norm = self.param("norm_scale", nn.initializers.ones, (inner,))
        with jax.named_scope("hvd.ssm"):
            y = mamba2_mixer(
                zxbcdt, conv, conv_bias, dt_bias, a_log, d, norm,
                heads=m.heads, head_dim=m.head_dim, groups=m.groups,
                state=m.state, chunk=m.chunk, eps=self.norm_eps)
        return dense(self.dim, name="out_proj")(y)

    def _relu2(self, h, width, prefix):
        """``relu(h W_up)^2 W_down``, bias-free, in ``dtype``: parameters
        ``{prefix}_up``, ``{prefix}_down``."""
        up = nn.Dense(width, use_bias=False, dtype=self.dtype,
                      name=f"{prefix}_up")(h)
        return nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                        name=f"{prefix}_down")(jnp.square(nn.relu(up)))

    def _swiglu(self, h, width, prefix):
        """``(silu(h W_gate) * (h W_up)) W_down``, bias-free, in ``dtype``:
        parameters ``{prefix}_gate``, ``{prefix}_up``, ``{prefix}_down``."""
        gate, up = (nn.Dense(width, use_bias=False, dtype=self.dtype,
                             name=f"{prefix}_{part}")(h)
                    for part in ("gate", "up"))
        return nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                        name=f"{prefix}_down")(nn.silu(gate) * up)

    def _routed(self, h):
        """The routed-expert FFN over the block's tokens, flattened: float32
        parameters, the router's product in float32, the experts' in
        ``dtype``."""
        from horovod_tpu.parallel.moe import routed_experts

        e = self.experts
        count = e.routed if e.count is None else e.count
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (self.dim, e.routed))
        inner = e.latent or self.dim
        gate = None
        if e.activation == "swiglu":
            gate = self.param("experts_gate", init, (count, inner, e.width))
        up = self.param("experts_up", init, (count, inner, e.width))
        down = self.param("experts_down", init, (count, e.width, inner))
        if e.scale != 1.0:
            # the routed sum is linear in the down projections: the factor
            # rides in their cast to ``dtype`` and costs no pass of its own
            down = down * e.scale
        x = h.reshape(-1, self.dim)
        routing = {}
        if e.router == "sigmoid":
            # the selection bias is a buffer, not a parameter: no gradient
            # trains it (a balancing rule would set it between steps)
            bias = jnp.zeros((e.routed,), jnp.float32)
            if self.has_variable("batch_stats", "router_bias") \
                    or self.is_mutable_collection("batch_stats"):
                bias = self.variable("batch_stats", "router_bias", jnp.zeros,
                                     (e.routed,), jnp.float32).value
            routing = dict(router_kind="sigmoid", bias=bias)
        if e.latent is not None:
            routing["route_from"] = x
            with jax.named_scope("hvd.moe_latent"):
                x = nn.Dense(e.latent, use_bias=False, dtype=self.dtype,
                             name="fc1_latent_proj")(x)
        y, rows = routed_experts(
            x, router, gate, up, down, top_k=e.top_k, first=e.first,
            select=e.select, dtype=self.dtype, **routing)
        # the step's counter rides where BatchNorm's statistics do: a step
        # builder hands it on, ``moe.record_rows`` reads it
        if self.is_mutable_collection("batch_stats"):
            self.variable("batch_stats", "moe_rows", jnp.zeros, (),
                          jnp.float32).value = rows
        if e.latent is not None:
            with jax.named_scope("hvd.moe_latent"):
                y = nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                             name="fc2_latent_proj")(y)
        y = y.reshape(h.shape)
        if e.shared is not None:
            # every token's own expert: it waits for nothing of the routing
            with jax.named_scope("hvd.moe_shared"):
                shared = (self._relu2 if e.activation == "relu2"
                          else self._swiglu)(h, e.shared, "shared")
                if e.shared_gate:
                    shared = shared * jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=self.dtype,
                        name="shared_expert_gate")(h))
                y = y + shared
        return y


class ZeroCentredRMSNorm(nn.Module):
    """``x rsqrt(mean(x^2) + eps) (1 + scale)`` over the last axis, in
    float32, ``scale`` zeros at start (Qwen3-Next's norm)."""

    epsilon: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + self.epsilon)
        return (x * (1.0 + scale)).astype(self.dtype)


def make_norm(kind: str, eps: float, dtype, name: str):
    """A block's (or the model's last) normalisation: flax's LayerNorm,
    RMSNorm (``x rsqrt(mean(x^2) + eps) scale``, no bias, no mean) or
    :class:`ZeroCentredRMSNorm` (``ZERO_CENTRED``)."""
    if kind == "layernorm":
        return nn.LayerNorm(epsilon=eps, dtype=dtype, name=name)
    if kind == "rmsnorm":
        return nn.RMSNorm(epsilon=eps, dtype=dtype, name=name)
    if kind == ZERO_CENTRED:
        return ZeroCentredRMSNorm(epsilon=eps, dtype=dtype, name=name)
    raise ValueError(f"norm must be 'layernorm', 'rmsnorm' or "
                     f"{ZERO_CENTRED!r}, got {kind!r}")


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
    # blocks from a per-layer description, one :class:`Layer` a block
    # (``depth`` of them, rotary positions); None: ``depth`` blocks alike
    # from heads / kv_heads / mlp_ratio above, as ever
    layers: Optional[Tuple[Layer, ...]] = None
    norm: str = "layernorm"  # or "rmsnorm": every block's and the last
    norm_eps: float = 1e-6

    def block_config(self, i: int) -> dict:
        """The fields of block ``i``: :class:`TransformerBlock` built from
        them, under the name ``block{i}``, is the model's i-th block."""
        common = dict(
            dim=self.dim, dtype=self.dtype, attention_fn=self.attention_fn,
            use_rope=self.pos_embedding == "rope", decode=self.decode,
            cache_len=self.cache_len or self.max_len, paged=self.paged,
            page_size=self.page_size, num_pages=self.num_pages,
            norm=self.norm, norm_eps=self.norm_eps)
        if self.layers is None:
            return dict(common, heads=self.heads, mlp_ratio=self.mlp_ratio,
                        kv_heads=self.kv_heads, rope_base=self.rope_base)
        layer = self.layers[i]
        routed = isinstance(layer.ffn, Experts)
        swiglu = isinstance(layer.ffn, SwiGLU)
        return dict(
            common, heads=layer.heads,
            mlp_ratio=0 if routed or swiglu or layer.ffn is None
            else layer.ffn,
            kv_heads=layer.kv_heads, rope_base=layer.rope_base,
            head_dim=layer.head_dim, yarn=layer.yarn, window=layer.window,
            experts=layer.ffn if routed else None,
            swiglu=layer.ffn if swiglu else None,
            rotary_dim=layer.rotary_dim, gate=layer.gate,
            qk_norm=layer.qk_norm, mixer=layer.mixer,
            has_ffn=layer.ffn is not None)

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = True,
                 page_table=None):
        if self.layers is not None and (
                len(self.layers) != self.depth
                or self.pos_embedding not in ("rope", "none")):
            raise ValueError(
                f"layers describes {len(self.layers)} blocks with rotary "
                f"positions or none: pass depth={len(self.layers)} and "
                f"pos_embedding='rope' or 'none' (got depth={self.depth}, "
                f"pos_embedding={self.pos_embedding!r})")
        if self.pos_embedding not in ("learned", "rope", "none"):
            raise ValueError(
                f"pos_embedding must be 'learned', 'rope' or 'none', "
                f"got {self.pos_embedding!r}"
            )
        if self.layers is not None:
            for i, layer in enumerate(self.layers):
                if layer.mixer is None and not (layer.heads
                                                or layer.head_dim):
                    if layer.ffn is None:
                        raise ValueError(
                            f"layers[{i}] has no heads, no mixer and no FFN: "
                            "a block takes attention or a mixer, an FFN, or "
                            "both")
                elif (layer.mixer is None) == (layer.heads < 1
                                               or layer.head_dim < 1):
                    raise ValueError(
                        f"layers[{i}] gives attention's heads and head_dim "
                        "or a mixer, one of the two: got heads="
                        f"{layer.heads}, head_dim={layer.head_dim}, "
                        f"mixer={layer.mixer}")
        head_dims = ([self.dim // self.heads] if self.layers is None
                     else [layer.head_dim for layer in self.layers])
        if self.pos_embedding == "rope" and any(d % 2 for d in head_dims):
            raise ValueError(
                f"rope needs an even head_dim, got {head_dims} "
                f"(dim={self.dim}, heads={self.heads})"
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
        if self.pos_embedding == "learned":
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
            x = TransformerBlock(**self.block_config(i), name=f"block{i}")(
                x, positions=positions if (use_rope or self.decode) else None,
                page_table=page_table)
        x = make_norm(self.norm, self.norm_eps, self.dtype, "ln_f")(x)
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


#: a gated-delta block's parameters besides its norms
_GATED_DELTA_PARAMS = ("in_proj_qkvz", "in_proj_ba", "conv1d", "A_log",
                       "dt_bias", "norm_scale", "out_proj")
#: a Mamba-2 block's
_MAMBA2_PARAMS = ("in_proj", "conv1d", "conv1d_bias", "A_log", "D",
                  "dt_bias", "norm_scale", "out_proj")


def transformer_param_specs(params, model_axis: str = "model"):
    """Tensor-parallel PartitionSpecs for a TransformerLM param tree
    (Megatron-style: qkv/up-proj sharded on the output dim, proj/down-proj on
    the input dim, so each block needs exactly one psum — which XLA inserts
    from these annotations; embeddings/vocab sharded on the feature axis)."""
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        name = "/".join(names)
        if any(n in _GATED_DELTA_PARAMS + _MAMBA2_PARAMS for n in names):
            raise ValueError(
                "transformer_param_specs has no layout for the gated-delta "
                f"layer or the Mamba-2 layer ({name}): its heads split over "
                "the in- and out-projections' grouped columns, which this "
                "function does not describe")
        if any(n in ("q_norm", "k_norm", "shared_expert_gate")
               for n in names):
            raise ValueError(
                "transformer_param_specs has no layout for a q/k norm or a "
                f"gated shared expert ({name}): no model it describes has "
                "one")
        if "router" in names or any(n.startswith("experts_") for n in names):
            raise ValueError(
                "transformer_param_specs has no layout for a routed-expert "
                f"block ({name}): its experts shard over an expert axis, "
                "which this function does not describe")
        if "gate_proj" in names or "mlp_gate" in names:
            raise ValueError(
                "transformer_param_specs has no layout for a gated or "
                f"SwiGLU block ({name}): no model it describes has one")
        if leaf.ndim < 2:
            return P()
        if ("qkv" in name or "mlp_up" in name or "q_proj" in name
                or "kv_proj" in name or "k_proj" in name
                or "v_proj" in name):
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

    found = sorted(set(block_params) & set(
        _GATED_DELTA_PARAMS + _MAMBA2_PARAMS + ("q_norm", "k_norm")))
    if found:
        raise ValueError(
            "tp_block_apply handles softmax-attention blocks only: "
            f"{found} belong to a gated-delta or Mamba-2 layer or to q/k "
            "norms")
    if "qkv" not in block_params:
        if "ln1" in block_params and "ln2" not in block_params:
            raise ValueError(
                "tp_block_apply handles blocks of attention and an MLP: this "
                f"one is of one part alone (params: {sorted(block_params)})")
        raise ValueError(
            "tp_block_apply requires a fused qkv kernel (kv_heads unset "
            "or == heads); GQA blocks need the GSPMD path "
            "(transformer_param_specs)")
    n = _axis_size(axis)
    r = jax.lax.axis_index(axis)
    dim = x.shape[-1]
    if heads % n:
        raise ValueError(f"heads={heads} not divisible by tp axis size {n}")
    if "mlp_up" not in block_params or "bias" not in block_params["ln1"]:
        raise ValueError(
            "tp_block_apply handles LayerNorm + MLP blocks only, not "
            "RMSNorm or routed-expert ones (params: "
            f"{sorted(block_params)})")
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

    refuse_training_only(model, "generate()")
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
