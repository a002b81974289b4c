"""Expert parallelism (Mixture-of-Experts) over the ``expert`` mesh axis.

No reference counterpart (SURVEY.md §2.7); TPU-native extension in the
GShard/Switch formulation, which is the shape XLA lowers best: routing as
one-hot einsum dispatch (dense matmuls on the MXU, no gather/scatter), token
exchange as a single ``lax.all_to_all`` per direction riding ICI.

Top-1 (Switch) routing with a static capacity factor: each token picks its
highest-gate expert; tokens beyond an expert's capacity are dropped (output
falls back to zero for them — the standard Switch behavior). Dispatch and
combine are the transpose of each other, so the layer is differentiable end
to end, router included (straight-through on the gate value).

:func:`routed_experts` is the other formulation, for many experts and
several a token, where the ``[T, E, C]`` one-hot would be the layer: top-k
routing without dropped tokens. The assignments to the experts a chip holds
are sorted by expert into a buffer of static shape that holds the worst
case, the experts' SwiGLU runs over its tiles in use as grouped matrix
products in Pallas kernels that also do what a row needs between them (the
activation, the combine's weighting, their backwards), and a further
kernel sums the weighted rows back per token, reading the rows held here.
The layer is told which experts it holds, routes over all of them and
computes its own experts' part; it exchanges nothing, so it serves one chip
(a model's share of a deployment), not yet the ``expert`` axis.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.observability import metrics as _metrics
from horovod_tpu.parallel.mesh import EXPERT_AXIS


def _build_dispatch(onehot, pos, gate, capacity):
    """[T,E,C] 0/1 dispatch + gate-weighted combine for one routing choice:
    token t lands in expert e's buffer slot pos[t] when it fits."""
    keep = pos < capacity
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32)               # [T, C]
    d = onehot[:, :, None] * pos_oh[:, None, :] * keep[:, None, None]
    return d, d * gate[:, None, None]


def top1_dispatch(gates_logits, capacity: int):
    """Switch-style top-1 routing tensors.

    Args:
      gates_logits: ``[T, E]`` router logits for T local tokens, E experts.
      capacity: per-expert buffer slots C.

    Returns:
      (dispatch ``[T, E, C]`` 0/1, combine ``[T, E, C]`` gate-weighted,
       aux_loss scalar — the Switch load-balancing loss).
    """
    t, e = gates_logits.shape
    gates = jax.nn.softmax(gates_logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)                  # [T]
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # [T, E]

    # position of each token within its expert's buffer (0-based; masked to
    # the selected expert BEFORE summing so other columns contribute nothing)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot        # [T, E]
    pos_in_expert = pos.sum(axis=-1)                         # [T]
    gate_val = (gates * onehot).sum(axis=-1)                 # [T]
    dispatch, combine = _build_dispatch(
        onehot, pos_in_expert, gate_val, capacity)

    # load-balancing aux loss (Switch Transformer eq. 4)
    density = onehot.mean(axis=0)
    density_proxy = gates.mean(axis=0)
    aux = (density * density_proxy).sum() * e
    return dispatch, combine, aux


def top2_dispatch(gates_logits, capacity: int):
    """GShard-style top-2 routing tensors (the GShard default; top-1 is the
    Switch simplification).

    Each token goes to its two highest-gate experts with combine weights
    renormalized over the pair. Buffer positions for second choices come
    after ALL first choices of that expert, so under pressure second
    choices drop first (the GShard policy). Same return shape/contract as
    :func:`top1_dispatch`.
    """
    t, e = gates_logits.shape
    gates = jax.nn.softmax(gates_logits.astype(jnp.float32), axis=-1)
    idx1 = jnp.argmax(gates, axis=-1)                        # [T]
    oh1 = jax.nn.one_hot(idx1, e, dtype=jnp.float32)
    gates2 = gates * (1.0 - oh1)                             # mask choice 1
    idx2 = jnp.argmax(gates2, axis=-1)
    oh2 = jax.nn.one_hot(idx2, e, dtype=jnp.float32)

    g1 = (gates * oh1).sum(axis=-1)
    g2 = (gates * oh2).sum(axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    pos1 = ((jnp.cumsum(oh1, axis=0) - 1.0) * oh1).sum(axis=-1)   # [T]
    count1 = oh1.sum(axis=0)                                 # [E]
    pos2_e = (jnp.cumsum(oh2, axis=0) - 1.0) * oh2 + count1[None, :] * oh2
    pos2 = pos2_e.sum(axis=-1)                               # [T]

    d1, c1 = _build_dispatch(oh1, pos1, g1, capacity)
    d2, c2 = _build_dispatch(oh2, pos2, g2, capacity)

    # aux loss on FIRST choices (GShard eq: fraction routed x mean gate)
    density = oh1.mean(axis=0)
    density_proxy = gates.mean(axis=0)
    aux = (density * density_proxy).sum() * e
    return d1 + d2, c1 + c2, aux


def expert_parallel_moe(router_params, expert_params, x, expert_fn: Callable,
                        *, axis_name: str = EXPERT_AXIS,
                        capacity_factor: float = 2.0,
                        routing: str = "top1"):
    """Apply an expert-parallel MoE FFN inside ``shard_map``.

    Args:
      router_params: ``[D, E_total]`` router weight (replicated).
      expert_params: this shard's experts' params, leading dim
        ``E_local = E_total / axis_size``.
      x: local tokens ``[T, D]`` (the caller's batch/seq shard).
      expert_fn: ``(one_expert_params, tokens [C', D]) -> [C', D]``, vmapped
        over local experts.
      capacity_factor: C = ceil(T / E_total * factor).
      routing: ``"top1"`` (Switch) or ``"top2"`` (GShard default).

    Returns:
      (output ``[T, D]``, aux_loss scalar)
    """
    n = lax.axis_size(axis_name)
    t, d = x.shape
    e_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    e_total = e_local * n
    capacity = max(int(-(-t * capacity_factor // e_total)), 1)  # ceil, static

    try:
        dispatch_fn = {"top1": top1_dispatch, "top2": top2_dispatch}[routing]
    except KeyError:
        raise ValueError(
            f"routing must be 'top1' or 'top2', got {routing!r}"
        ) from None

    logits = x.astype(jnp.float32) @ router_params   # [T, E_total]
    dispatch, combine, aux = dispatch_fn(logits, capacity)

    # dispatch MY tokens into per-expert buffers: [E_total, C, D], ordered so
    # block [k*E_local, (k+1)*E_local) belongs to shard k's experts
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # exchange: shard k receives ITS experts' buffers from every shard,
    # stacked on the capacity axis -> [E_local, n*C, D]
    expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                               concat_axis=1, tiled=True)

    out = jax.vmap(expert_fn)(expert_params, expert_in)      # [E_local, n*C, D]

    # inverse exchange: every shard gets back its C slots from each expert
    # -> [E_total, C, D] in the same global-expert order as dispatch
    out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                         tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, out)
    # aux loss averaged over shards (each shard routed its own tokens)
    aux = lax.pmean(aux, axis_name)
    return y.astype(x.dtype), aux


# --------------------------------------------------------------------------
# routing without dropped tokens: sort + grouped matrix products

#: rows of one tile of the sorted buffer. Every held expert's rows start on
#: a tile, so a tile multiplies one expert's matrix: no masks in the
#: kernels, at up to a tile of padding rows an expert (a v5e chip, [R, 2304]
#: x [16, 2304, 896] bfloat16 with 16,318 rows in use, ms a product:
#: ``lax.ragged_dot`` 1.97, jax's megablox ``gmm`` 0.67-1.15 by tiling, the
#: dense product of as many rows 0.40; PERF.md section 6, PR 35)
TILE_ROWS = 256
#: VMEM the grouped products ask Mosaic for: a row tile, one expert's matrix
#: whole and the result tile, double-buffered
_VMEM_LIMIT = 48 << 20
#: tokens one grid step of the way back (:func:`_pallas_combine`) sums, and
#: the most rows of one block it reads of the sorted buffer for them: the
#: rows a token tile has on one held expert follow each other there, so one
#: window a (tile, expert) as a rule (:func:`_combine_tile`)
TOKEN_TILE = 256
WINDOW_ROWS = 64
#: rows one product of the way back takes at once: the held experts in
#: groups, a window each, ``[TOKEN_TILE, group x window]`` times ``[group x
#: window, D]``
_PRODUCT_ROWS = 1024


def route_top_k(x, router, top_k: int, select=None, *,
                kind: str = "softmax", bias=None):
    """The router in float32: probabilities over every routed expert, the
    ``top_k`` largest and their weights normalised to sum to one. ``x``
    ``[T, D]``, ``router`` ``[D, E]`` -> weights ``[T, k]`` f32, experts
    ``[T, k]`` int32. Where ``select`` is given, a token's experts are the
    ``top_k`` of ``select(probabilities)`` ``[T, E]`` instead; their
    weights are the router's all the same.

    ``kind`` ``"sigmoid"`` (DeepSeek-V3's, Nemotron-H's): ``s =
    sigmoid(x router)``, the experts the ``top_k`` of ``s + bias`` (a
    selection bias ``[E]`` that no gradient reaches; zeros where none is
    given), or of ``select(s)``, weighed by their ``s`` over the chosen
    ``s``' sum."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if kind == "sigmoid":
        return _route_sigmoid(logits, top_k, select, bias)
    if kind != "softmax":
        raise ValueError(f"kind must be 'softmax' or 'sigmoid', got {kind!r}")
    probs = jax.nn.softmax(logits, axis=-1)
    _, experts = lax.top_k(probs if select is None else select(probs), top_k)
    # the chosen logits by a one-hot product: its gradient is a product
    # too, where ``top_k``'s own is a scatter. Their softmax is the chosen
    # probabilities over their sum, and stays finite where a token's chosen
    # experts all have probabilities that round to nothing
    chosen = jax.nn.one_hot(experts, logits.shape[-1], dtype=logits.dtype)
    return jax.nn.softmax(jnp.sum(logits[:, None, :] * chosen, axis=-1),
                          axis=-1), experts


def _route_sigmoid(logits, top_k: int, select, bias):
    """:func:`route_top_k`'s ``sigmoid`` form from the float32 logits."""
    scores = jax.nn.sigmoid(logits)
    if select is not None:
        choice = select(scores)
    elif bias is not None:
        choice = scores + lax.stop_gradient(bias.astype(jnp.float32))
    else:
        choice = scores
    _, experts = lax.top_k(choice, top_k)
    # the chosen scores by a one-hot product, as the softmax form takes its
    # logits: its gradient is a product too
    chosen = jax.nn.one_hot(experts, logits.shape[-1], dtype=logits.dtype)
    picked = jnp.sum(scores[:, None, :] * chosen, axis=-1)
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20), experts


def buffer_rows(tokens: int, top_k: int, count: int) -> int:
    """Rows of the sorted buffer: the worst case, every assignment held
    here, and a tile of padding an expert, so no assignment is ever without
    a row. A token's ``top_k`` experts are distinct, so at most ``min(top_k,
    count)`` of its slots land on the ``count`` held here: with 22 chosen of
    512 and 8 held, 8 a token, not 22. Nothing holds a router near balance
    (untrained, the chip read 0 to 30,639 of 65,536 assignments on 16 of 64
    experts, layer by layer and step by step, where balance sends 16,384).
    The grouped products, with the activation and the weighting inside them
    (:func:`expert_mlp`), pass over the tiles no row fills, and the way
    back to the tokens reads the rows held here (:func:`_pallas_combine`);
    the gather into the buffer (:func:`_to_rows`, and the like of it that
    is the way back's transpose) and the plan's tables of a number a row
    still run over the whole of it."""
    return (-(-tokens * min(top_k, count) // TILE_ROWS) + count) * TILE_ROWS


def _plan(experts, *, first: int, count: int):
    """Where each assignment goes. ``experts`` ``[T, k]``: the routed
    expert of each of a token's slots. The slots whose expert is held here
    (``first <= e < first + count``) are laid out by expert, each expert's
    run starting on a tile of ``TILE_ROWS`` rows (an expert with no slot
    keeps one tile: its weight gradient is written there), in a buffer of
    :func:`buffer_rows` rows. Returns a dict of int32 arrays:
    ``row_of_slot`` ``[T * k]`` (the buffer row of each slot; the buffer's
    length for a slot not held here), ``slot_of_row`` ``[rows]`` (``T * k``
    for a padding row), ``tile_expert`` ``[rows / TILE_ROWS]`` (a tile past
    the last expert's rows reads as the last expert's), ``tiles`` ``[1]``
    (the tiles in use: the experts' runs fill the buffer's first ``tiles``
    tiles), ``seg_start`` and ``seg_rows`` ``[ceil(T / TOKEN_TILE), count]``
    (the first buffer row and the number of rows that a tile of
    ``TOKEN_TILE`` tokens has on each held expert: the sort is stable, so
    they follow each other) and the counter ``local`` (slots held here)."""
    tokens, top_k = experts.shape
    slots = tokens * top_k
    rows = buffer_rows(tokens, top_k, count)
    local = experts.reshape(slots) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    # sorted by expert, slots not held here last; stable: token order
    # within an expert
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)
    # tables of ``count`` entries are read by a comparison against every
    # held expert, and per tile, not per row: ``bincount`` is a scatter, and
    # a gather of scalars costs the chip 15 ns an element
    mine = key[:, None] == jnp.arange(count)[None, :]            # [slots, E]
    # by tile of TOKEN_TILE tokens first: the sort is stable, so the rows a
    # token tile has on one expert are one run of that expert's rows
    token_tiles = -(-tokens // TOKEN_TILE)
    seg_rows = jnp.sum(
        jnp.pad(mine, ((0, token_tiles * TOKEN_TILE * top_k - slots), (0, 0))
                ).reshape(token_tiles, TOKEN_TILE * top_k, count),
        axis=1, dtype=jnp.int32)                                 # [tiles, E]
    sizes = jnp.sum(seg_rows, axis=0)
    starts = jnp.cumsum(sizes) - sizes
    tiles_of = jnp.maximum(-(-sizes // TILE_ROWS), 1)
    tile_ends = jnp.cumsum(tiles_of)
    row_starts = (tile_ends - tiles_of) * TILE_ROWS
    seg_start = (row_starts[None, :] + jnp.cumsum(seg_rows, axis=0)
                 - seg_rows).astype(jnp.int32)
    n_tiles = rows // TILE_ROWS
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_ends, jnp.arange(n_tiles), side="right",
                         method="compare_all"),
        count - 1).astype(jnp.int32)

    row = rank + jnp.sum(jnp.where(mine, (row_starts - starts)[None, :], 0),
                         axis=1)
    row_of_slot = jnp.where(key < count, row, rows).astype(jnp.int32)

    within = (jnp.arange(rows, dtype=jnp.int32).reshape(n_tiles, TILE_ROWS)
              - row_starts[tile_expert][:, None])                # [tiles, T]
    real = (within >= 0) & (within < sizes[tile_expert][:, None])
    sorted_at = jnp.clip(starts[tile_expert][:, None] + within, 0, slots - 1)
    slot_of_row = jnp.where(real, order[sorted_at], slots).reshape(
        rows).astype(jnp.int32)
    return {
        "row_of_slot": row_of_slot, "slot_of_row": slot_of_row,
        "seg_start": seg_start, "seg_rows": seg_rows,
        "tile_expert": tile_expert,
        "tiles": tile_ends[-1:].astype(jnp.int32), "local": jnp.sum(sizes),
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _to_rows(x, plan, top_k: int, routed: int, interpret: bool):
    """``x`` ``[T, ...]`` -> ``[rows, ...]``: each buffer row its slot's
    token's row of ``x`` (token 0's for a padding row). ``plan`` is
    :func:`_plan`'s over ``routed`` experts. Its transpose is no scatter-add
    (:func:`_to_tokens`): one of as many rows takes ten times a gather's
    time on the chip."""
    return x[jnp.minimum(plan["slot_of_row"] // top_k, x.shape[0] - 1)]


def _to_rows_fwd(x, plan, top_k, routed, interpret):
    return _to_rows(x, plan, top_k, routed, interpret), plan


@jax.named_scope("hvd.moe_route")
def _to_rows_bwd(top_k, routed, interpret, plan, g):
    return _to_tokens(g, plan, top_k, routed, interpret), None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


def _gather_to_tokens(y, row_of_slot, top_k: int):
    """:func:`_to_tokens` by a gather of every slot's row, the slots
    without one clamped onto the buffer's last row and chosen away after:
    ``T * top_k`` rows read whatever share of them is held here."""
    has_row = row_of_slot < y.shape[0]
    rows = y[jnp.minimum(row_of_slot, y.shape[0] - 1)]
    rows = jnp.where(has_row.reshape((-1,) + (1,) * (y.ndim - 1)),
                     rows.astype(jnp.float32), 0)
    return jnp.sum(rows.reshape(-1, top_k, *y.shape[1:]),
                   axis=1).astype(y.dtype)


def _combine_tile(top_k: int, routed: int, count: int, align: int):
    """The way back's window rows and the windows one product takes, from
    the shapes. Where the choice of experts is even, a tile of tokens has
    ``TOKEN_TILE * top_k / routed`` rows on a held expert, and its first
    window starts up to ``align - 1`` rows before them: the window is the
    least power of two from ``align`` that holds both, at most
    ``WINDOW_ROWS``. A product takes ``_PRODUCT_ROWS`` rows of windows, so
    at 32 held experts of 5-8 rows a tile the windows are 32 rows and one
    group holds them all: only the first group's copies are started a tile
    ahead, a later group's wait in its own round."""
    segment = -(-TOKEN_TILE * top_k // routed)
    window = align
    while window < min(segment + align - 1, WINDOW_ROWS):
        window *= 2
    return window, min(count, _PRODUCT_ROWS // window)


def _combine_kernel(start_ref, size_ref, slot_rows_ref, y_ref, out_ref, buf,
                    sem, acc, *, count: int, window: int, windows: int,
                    align: int):
    """One tile of tokens: the float32 sum of each token's rows of the
    sorted buffer ``y_ref`` (in HBM). The rows the tile has on held expert
    ``e`` are ``size_ref[e]`` rows from ``start_ref[e]`` on; they are read
    as windows of ``window`` rows from an aligned row, a group of
    ``windows`` experts' windows at a time, and summed by one 0/1 product,
    ``P[t, r]`` being "window row ``r`` is one of token ``t``'s rows". A
    window's rows that are not the segment's may never have been written
    (or be another tile's): they are zeroed before the product, where ``0
    x NaN`` would be NaN. A segment longer than a window takes further
    rounds. The first group's windows of the next tile are copied while
    this one is summed (the grid runs in order), into the other half of
    ``buf``. The experts are walked by ``fori_loop``: sixteen unrolled
    bodies a call site, eight sites a step, cost the step's set-up half a
    minute of tracing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, half = pl.program_id(0), pl.program_id(0) % 2
    rows = y_ref.shape[0]
    slot_rows = slot_rows_ref[...]                       # [tokens, top_k]

    def segment(tile, e):
        """Expert ``e``'s rows of the tile, and the aligned row its first
        window starts on."""
        start, size = start_ref[tile * count + e], size_ref[tile * count + e]
        return start, size, start // align * align

    def part(tile, e, w):
        """Round ``w`` of expert ``e``: of its segment the rows ``[lo,
        hi)`` (none: ``hi <= lo``), in the window read from row ``at``
        (kept inside the buffer)."""
        start, size, first = segment(tile, e)
        first += w * window
        return (jnp.maximum(start, first),
                jnp.minimum(start + size, first + window),
                jnp.minimum(first, rows - window))

    def copy(at, half, k):
        return pltpu.make_async_copy(
            y_ref.at[pl.ds(pl.multiple_of(at, align), window), :],
            buf.at[half, pl.ds(pl.multiple_of(k * window, window), window),
                   :], sem.at[half, k])

    def start(tile, group, held, w, half):
        def one(k, carry):
            lo, hi, at = part(tile, group + k, w)
            pl.when(hi > lo)(copy(at, half, k).start)
            return carry

        lax.fori_loop(0, held, one, None)

    pl.when(tile == 0)(lambda: start(0, 0, windows, 0, 0))
    pl.when(tile + 1 < pl.num_programs(0))(
        lambda: start(tile + 1, 0, windows, 0, 1 - half))
    acc[...] = jnp.zeros_like(acc)

    for group in range(0, count, windows):
        held = min(windows, count - group)
        width = held * window

        def one_round(w, carry, group=group, held=held, width=width):
            # round 0 of the first group was started a tile ahead
            pl.when((w > 0) | (group > 0))(
                lambda: start(tile, group, held, w, half))
            # the buffer row each column of the product's left side stands
            # for, and the 0/1 matrix, while the copies run
            col = lax.broadcasted_iota(jnp.int32, (1, width), 1)

            def rows_of(k, row_of_col):
                at = part(tile, group + k, w)[2]
                here = (col >= k * window) & (col < (k + 1) * window)
                return jnp.where(here, col + (at - k * window), row_of_col)

            row_of_col = lax.fori_loop(0, held, rows_of, col)
            chosen = slot_rows[:, 0:1] == row_of_col
            for j in range(1, slot_rows.shape[1]):
                chosen |= slot_rows[:, j:j + 1] == row_of_col

            def land(k, carry):
                lo, hi, at = part(tile, group + k, w)
                pl.when(hi > lo)(copy(at, half, k).wait)
                span = pl.ds(pl.multiple_of(k * window, window), window)
                row = at + lax.broadcasted_iota(jnp.int32, (window, 1), 0)
                buf[half, span, :] = jnp.where((row >= lo) & (row < hi),
                                               buf[half, span, :], 0)
                return carry

            lax.fori_loop(0, held, land, None)
            acc[...] += lax.dot_general(
                chosen.astype(buf.dtype), buf[half, pl.ds(0, width), :],
                (((1,), (0,)), ((), ())),
                precision=(lax.Precision.HIGHEST
                           if buf.dtype == jnp.float32 else None),
                preferred_element_type=jnp.float32)
            return carry

        def rounds(k, most, group=group):
            start_, size, first = segment(tile, group + k)
            return jnp.maximum(most, jnp.where(
                size > 0, -(-(start_ + size - first) // window), 0))

        lax.fori_loop(0, lax.fori_loop(0, held, rounds, 0), one_round, None)

    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("top_k", "window", "windows",
                                             "interpret"))
def _pallas_combine(y, plan, *, top_k: int, window: int, windows: int,
                    interpret: bool):
    """:func:`_to_tokens` on a lane-wide ``[rows, D]`` operand as a Pallas
    kernel (:func:`_combine_kernel`): it reads the rows held here, in
    windows (:func:`_combine_tile`), and not a row for every slot. The call
    carries no ``name=``:
    its device time is its scope's, ``hvd.moe_route``. Jitted, so that a
    step's call sites (a layer's combine and its dispatch's transpose,
    layer after layer) trace and lower the kernel once between them; each
    keeps the ``op_name`` of its own scope."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = y.shape
    token_tiles, count = plan["seg_start"].shape
    tokens = plan["row_of_slot"].shape[0] // top_k
    padded = token_tiles * TOKEN_TILE
    slot_rows = jnp.pad(plan["row_of_slot"].reshape(tokens, top_k),
                        ((0, padded - tokens), (0, 0)), constant_values=rows)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, count=count, window=window,
                          windows=windows, align=32 // y.dtype.itemsize),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(token_tiles,),
            in_specs=[
                pl.BlockSpec((TOKEN_TILE, top_k), lambda i, s, n: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((TOKEN_TILE, d), lambda i, s, n: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, windows * window, d), y.dtype),
                pltpu.SemaphoreType.DMA((2, windows)),
                pltpu.VMEM((TOKEN_TILE, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((padded, d), y.dtype),
        # in order: a tile's first windows are copied during the one before
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(plan["seg_start"].reshape(-1), plan["seg_rows"].reshape(-1),
      slot_rows, y)
    return out[:tokens]


def _combine_fits(d: int, itemsize: int, group: int) -> bool:
    """Whether :func:`_combine_kernel` has room at rows of ``d`` elements
    and ``group`` rows of windows a product: both halves of a group's
    windows, the float32 sum and a product's result, the block of the
    result twice and the 0/1 matrix."""
    return (2 * group * d * itemsize + 2 * TOKEN_TILE * d * 4
            + 2 * TOKEN_TILE * d * itemsize
            + TOKEN_TILE * group * (4 + itemsize)) <= _VMEM_LIMIT * 3 // 4


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _to_tokens(y, plan, top_k: int, routed: int, interpret: bool):
    """``y`` ``[rows, ...]`` -> ``[T, ...]``: each token the float32 sum of
    its slots' buffer rows (a slot with no row adds nothing). ``plan`` is
    :func:`_plan`'s over ``routed`` experts. The operand's shape chooses
    the form: ``[rows, D]`` in whole lanes (that fit the kernel's VMEM),
    summed ``top_k`` rows a token as the plan was made, is the kernel's,
    its windows sized from the shapes (:func:`_combine_tile`); anything
    else (the router weights' ``[rows, 1]``) gathers."""
    row_of_slot = plan["row_of_slot"]
    token_tiles, count = plan["seg_start"].shape
    window, windows = _combine_tile(top_k, routed, count,
                                    32 // y.dtype.itemsize)
    if (y.ndim == 2 and y.shape[1] % 128 == 0
            and token_tiles == -(-(row_of_slot.shape[0] // top_k)
                                 // TOKEN_TILE)
            and _combine_fits(y.shape[1], y.dtype.itemsize,
                              windows * window)):
        if _metrics.enabled():
            for dim, n in (("tokens", TOKEN_TILE), ("rows", window),
                           ("windows", windows)):
                _metrics.gauge(
                    "moe_combine_tile",
                    help="tokens one grid step of the routed layer's way "
                         "back to the tokens sums, rows of one window it "
                         "reads of the sorted buffer, and windows one "
                         "product takes, chosen from the shapes; absent "
                         "where the gather ran", dim=dim).set(n)
        return _pallas_combine(y, plan, top_k=top_k, window=window,
                               windows=windows, interpret=interpret)
    return _gather_to_tokens(y, row_of_slot, top_k)


def _to_tokens_fwd(y, plan, top_k, routed, interpret):
    return _to_tokens(y, plan, top_k, routed, interpret), plan


@jax.named_scope("hvd.moe_route")
def _to_tokens_bwd(top_k, routed, interpret, plan, g):
    # zeros on the padding rows: their slot is ``T * top_k``, which reads
    # the row of zeros put after ``g``, and not a choice over ``[rows,
    # ...]`` behind the gather
    zeros = jnp.zeros((1,) + g.shape[1:], g.dtype)
    return _to_rows(jnp.concatenate([g, zeros]), plan, top_k, routed,
                    interpret), None


_to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


def _dot(lhs, rhs, transpose_rhs: bool = False):
    """``lhs @ rhs`` (or ``lhs @ rhs^T``) with float32 accumulation."""
    return lax.dot_general(
        lhs, rhs, (((1,), (1 if transpose_rhs else 0,)), ((), ())),
        preferred_element_type=jnp.float32)


def hvd_moe_gmm(tile_expert_ref, tiles_ref, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs: bool):
    """One row tile of the buffer times its expert's matrix (or its
    transpose). A tile past the ``tiles_ref[0]`` in use is passed over: the
    index maps keep the last tile in use in place, so nothing is copied
    for it either, and its rows of the result stay as they were
    allocated. The work follows the rows the router sent here."""
    from jax.experimental import pallas as pl

    del tile_expert_ref                    # read by the index maps

    @pl.when(pl.program_id(0) < tiles_ref[0])
    def _multiply():
        out_ref[...] = _dot(lhs_ref[...], rhs_ref[0], transpose_rhs).astype(
            out_ref.dtype)


def hvd_moe_tgmm(tile_expert_ref, tiles_ref, lhs_ref, *refs):
    """An expert's weight gradients ``lhs^T dout`` for each of ``refs``'
    first half, the ``dout``s that share ``lhs``, summed over its row
    tiles in ``refs``' second half, the float32 output blocks that stay in
    VMEM while the grid walks one expert's tiles (padding rows add zeros:
    their ``dout`` is; the tiles past those in use are passed over)."""
    from jax.experimental import pallas as pl

    douts, outs = refs[:len(refs) // 2], refs[len(refs) // 2:]
    i = pl.program_id(2)
    before = tile_expert_ref[jnp.maximum(i - 1, 0)]

    @pl.when(jnp.logical_or(i == 0, tile_expert_ref[i] != before))
    def _init():
        for out_ref in outs:
            out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < tiles_ref[0])
    def _add():
        lhs = lhs_ref[...]
        for dout_ref, out_ref in zip(douts, outs):
            out_ref[0] += lax.dot_general(
                lhs, dout_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _fit_block(n: int, cap: int) -> int:
    """The widest block of at most ``cap`` columns that divides ``n`` in
    whole lanes, else ``n``."""
    return next((b for b in range(cap - cap % 128, 127, -128) if n % b == 0),
                n) if n > cap else n


def _in_use(i, tiles):
    """Tile ``i``, or the last tile in use where ``i`` lies past it: a
    block index that does not change asks for no copy."""
    return jnp.minimum(i, tiles[0] - 1)


def _row_tiles(width: int):
    """The block of a ``[rows, width]`` operand or result that grid step
    ``i`` of a grouped product holds: row tile ``i`` (:func:`_in_use`)."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((TILE_ROWS, width),
                        lambda i, te, n: (_in_use(i, n), 0))


def _row_scalars():
    """A number a row, lane-dense: tile ``i``'s block of a ``[1, rows]``
    array (a ``[rows, 1]`` column is padded to 128 lanes in HBM)."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, TILE_ROWS), lambda i, te, n: (0, _in_use(i, n)))


def _matrix_of_tile(rhs):
    """The matrix of tile ``i``'s expert, whole. One expert's tiles follow
    each other: its matrix is copied once."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1,) + rhs.shape[1:], lambda i, te, n: (te[i], 0, 0))


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def _pallas_gmm(lhs, rhs, tile_expert, tiles, *, transpose_rhs: bool,
                interpret: bool):
    n_out = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    matrix_bytes = rhs.shape[1] * rhs.shape[2] * rhs.dtype.itemsize
    if 2 * matrix_bytes > _VMEM_LIMIT // 2:
        raise ValueError(
            f"routed experts: an expert's matrix {rhs.shape[1:]} in "
            f"{rhs.dtype} does not fit the grouped product's VMEM whole "
            f"({matrix_bytes} bytes); this kernel has no tiling over it")
    return _grouped_call(
        functools.partial(hvd_moe_gmm, transpose_rhs=transpose_rhs),
        (tile_expert, tiles, lhs, rhs),
        [_row_tiles(lhs.shape[1]), _matrix_of_tile(rhs)],
        [(n_out, lhs.dtype)], interpret=interpret, name="hvd_moe_gmm")[0]


@functools.partial(jax.jit, static_argnames=("count", "interpret"))
def _pallas_tgmm(lhs, douts, tile_expert, tiles, count: int, *,
                 interpret: bool):
    """``lhs^T dout`` by expert for each of the tuple ``douts`` (of one
    shape), ``lhs`` read once for all of them: a tuple of ``[count, K, N]``
    float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = lhs.shape
    n = douts[0].shape[1]
    # the float32 output blocks [bk, bn], one a dout, double-buffered,
    # beside the tiles: whole matrices where they fit (a grid step costs
    # 0.35 us whatever it holds, and a block more is a grid as long again)
    cap = _VMEM_LIMIT * 3 // 4 // (8 * len(douts))
    bk, bn = ((_fit_block(k, cap // n), n) if k >= n
              else (k, _fit_block(n, cap // k)))
    dout_spec = pl.BlockSpec((TILE_ROWS, bn),
                             lambda a, b, i, te, n: (_in_use(i, n), b))
    out_spec = pl.BlockSpec((1, bk, bn),
                            lambda a, b, i, te, n: (te[i], a, b))
    return pl.pallas_call(
        hvd_moe_tgmm,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // bk, n // bn, rows // TILE_ROWS),
            in_specs=[
                pl.BlockSpec((TILE_ROWS, bk),
                             lambda a, b, i, te, n: (_in_use(i, n), a)),
                *[dout_spec] * len(douts)],
            out_specs=[out_spec] * len(douts),
        ),
        out_shape=[jax.ShapeDtypeStruct((count, k, n), jnp.float32)
                   ] * len(douts),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hvd_moe_tgmm",
    )(tile_expert, tiles, lhs, *douts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(lhs, rhs, tile_expert, tiles, interpret: bool = False):
    """``lhs`` ``[rows, K]`` times, tile of ``TILE_ROWS`` rows by tile,
    the matrix ``rhs[tile_expert[i]]`` of ``rhs`` ``[E, K, N]`` (float32
    parameters, multiplied in ``lhs``'s dtype with float32 accumulation)
    -> ``[rows, N]``, over the first ``tiles[0]`` tiles: the rows of the
    result past them are not written, and read as anything. The gradient
    of ``rhs`` is float32: one expert's rows are whole tiles that follow
    each other, and every expert has one at least among those in use."""
    return _pallas_gmm(lhs, rhs.astype(lhs.dtype), tile_expert, tiles,
                       transpose_rhs=False, interpret=interpret)


def _grouped_matmul_fwd(lhs, rhs, tile_expert, tiles, interpret):
    return grouped_matmul(lhs, rhs, tile_expert, tiles, interpret), (
        lhs, rhs, tile_expert, tiles)


@jax.named_scope("hvd.moe_experts")
def _grouped_matmul_bwd(interpret, res, g):
    lhs, rhs, tile_expert, tiles = res
    g = g.astype(lhs.dtype)
    dlhs = _pallas_gmm(g, rhs.astype(lhs.dtype), tile_expert, tiles,
                       transpose_rhs=True, interpret=interpret)
    drhs, = _pallas_tgmm(lhs, (g,), tile_expert, tiles, rhs.shape[0],
                         interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def hvd_moe_mlp_fwd(tile_expert_ref, tiles_ref, x_ref, w_ref, gate_ref,
                    up_ref, down_ref, g_ref, u_ref, act_ref, y_ref):
    """One row tile through its expert's SwiGLU: times the gate and up
    matrices, the weighted activation ``silu(g) u w`` from the float32
    accumulators, rounded once, times the down matrix. The product with the
    down matrix is linear in the activation, so the combine's weighting
    rides there, ``F`` wide and not ``D``. ``g`` and ``u`` are kept,
    rounded, for the backward, and the weighted activation for the down
    matrix's gradient. ``w_ref`` holds the tile's weights as a row; a
    padding row's is 0."""
    from jax.experimental import pallas as pl

    del tile_expert_ref                    # read by the index maps

    @pl.when(pl.program_id(0) < tiles_ref[0])
    def _multiply():
        x = x_ref[...]
        g, u = _dot(x, gate_ref[0]), _dot(x, up_ref[0])
        g_ref[...] = g.astype(g_ref.dtype)
        u_ref[...] = u.astype(u_ref.dtype)
        act = (jax.nn.silu(g) * u * w_ref[...].reshape(-1, 1)).astype(
            act_ref.dtype)
        act_ref[...] = act
        y_ref[...] = _dot(act, down_ref[0]).astype(y_ref.dtype)


def hvd_moe_mlp_bwd(tile_expert_ref, tiles_ref, dy_ref, w_ref, g_ref, u_ref,
                    gate_ref, up_ref, down_ref, dg_ref, du_ref, dw_ref,
                    dx_ref):
    """The backward of :func:`hvd_moe_mlp_fwd` for a tile of ``d ys``, in
    float32 on the accumulator ``t = d ys . down^T``: ``d w = rowsum(t
    act)`` (``<d ys, act . down> = <d ys . down^T, act>``: the unweighted
    result is neither kept nor made again), written as a row; ``d act = t
    w``; ``d g`` and ``d u`` by silu's derivative, with ``act = silu(g) u``
    from the kept ``g`` and ``u``, rounded for the matrices' gradients;
    and ``d xs = d g . gate^T + d u . up^T`` summed in float32 and rounded
    once (autodiff would write both and add them over the whole
    buffer)."""
    from jax.experimental import pallas as pl

    del tile_expert_ref

    @pl.when(pl.program_id(0) < tiles_ref[0])
    def _multiply():
        t = _dot(dy_ref[...], down_ref[0], transpose_rhs=True)
        g = g_ref[...].astype(jnp.float32)
        u = u_ref[...].astype(jnp.float32)
        gate = jax.nn.sigmoid(g)
        silu = g * gate
        dw_ref[...] = jnp.sum(t * silu * u, axis=1)[None, :]
        dact = t * w_ref[...].reshape(-1, 1)
        du = (dact * silu).astype(du_ref.dtype)
        dg = (dact * u * (gate * (1 + g * (1 - gate)))).astype(dg_ref.dtype)
        du_ref[...] = du
        dg_ref[...] = dg
        dx_ref[...] = (_dot(dg, gate_ref[0], transpose_rhs=True)
                       + _dot(du, up_ref[0], transpose_rhs=True)
                       ).astype(dx_ref.dtype)


def _grouped_call(kernel, operands, in_specs, results, *, interpret: bool,
                  name: Optional[str] = None):
    """``kernel`` over the buffer's row tiles, ``plan``'s ``tile_expert``
    and ``tiles`` by scalar prefetch (the first two ``operands``);
    ``results`` are ``(width, or None for a number a row, dtype)``. The
    call is named ``name``, else as ``kernel`` is."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = operands[2].shape[0]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // TILE_ROWS,),
            in_specs=in_specs,
            out_specs=[_row_scalars() if width is None else _row_tiles(width)
                       for width, _ in results],
        ),
        out_shape=[jax.ShapeDtypeStruct(
            (1, rows) if width is None else (rows, width), dtype)
            for width, dtype in results],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name or kernel.__name__,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_mlp_fwd(xs, w, gate, up, down, tile_expert, tiles, *,
                    interpret: bool):
    d, f = gate.shape[1:]
    return _grouped_call(
        hvd_moe_mlp_fwd, (tile_expert, tiles, xs, w, gate, up, down),
        [_row_tiles(d), _row_scalars(), _matrix_of_tile(gate),
         _matrix_of_tile(up), _matrix_of_tile(down)],
        [(f, xs.dtype)] * 3 + [(d, xs.dtype)], interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_mlp_bwd(dys, w, g, u, gate, up, down, tile_expert, tiles, *,
                    interpret: bool):
    d, f = gate.shape[1:]
    return _grouped_call(
        hvd_moe_mlp_bwd, (tile_expert, tiles, dys, w, g, u, gate, up, down),
        [_row_tiles(d), _row_scalars(), _row_tiles(f), _row_tiles(f),
         _matrix_of_tile(gate), _matrix_of_tile(up), _matrix_of_tile(down)],
        [(f, dys.dtype), (f, dys.dtype), (None, jnp.float32),
         (d, dys.dtype)], interpret=interpret)


def _experts_fit(d: int, f: int, itemsize: int) -> bool:
    """Whether the fused calls have room at experts of ``[d, f]``: an
    expert's three matrices, double-buffered, beside (the backward holds
    most) two tiles of ``d`` and four of ``f`` elements a row, twice each,
    and the float32 accumulators: one of ``d`` and six of ``f`` a row."""
    return (6 * d * f * itemsize
            + TILE_ROWS * (2 * (2 * d + 4 * f) * itemsize + (d + 6 * f) * 4)
            ) <= _VMEM_LIMIT * 7 // 8


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def expert_mlp(xs, w_rows, gate, up, down, tile_expert, tiles,
               interpret: bool = False):
    """The held experts' SwiGLU over the sorted buffer and the combine's
    weighting, ``w_rows * ((silu(xs gate[e]) * (xs up[e])) down[e])`` tile
    by tile of ``TILE_ROWS`` rows with ``e = tile_expert[i]``, over the
    first ``tiles[0]`` tiles: ``xs`` ``[rows, D]``, ``w_rows`` ``[rows]``
    float32 (0 for a padding row), ``gate`` and ``up`` ``[E, D, F]`` and
    ``down`` ``[E, F, D]`` (float32 parameters, multiplied in ``xs``'s
    dtype with float32 accumulation) -> ``[rows, D]``. One derivative for
    the whole of it, so that everything a row needs between the products
    is done on the tile a product holds, in float32 on its accumulator,
    and only for the tiles in use: one call forward
    (:func:`hvd_moe_mlp_fwd`), three backward (:func:`hvd_moe_mlp_bwd`,
    and :func:`hvd_moe_tgmm` for the gate's and the up's gradients
    together and for the down's). The rows past the tiles in use are
    neither written nor read, in any result."""
    return _expert_mlp_fwd(xs, w_rows, gate, up, down, tile_expert, tiles,
                           interpret)[0]


def _expert_mlp_fwd(xs, w_rows, gate, up, down, tile_expert, tiles,
                    interpret):
    w = w_rows.reshape(1, -1)
    g, u, act, ys = _pallas_mlp_fwd(
        xs, w, *(m.astype(xs.dtype) for m in (gate, up, down)), tile_expert,
        tiles, interpret=interpret)
    return ys, (xs, w, g, u, act, gate, up, down, tile_expert, tiles)


@jax.named_scope("hvd.moe_experts")
def _expert_mlp_bwd(interpret, res, dys):
    xs, w, g, u, act, gate, up, down, tile_expert, tiles = res
    dys = dys.astype(xs.dtype)
    dg, du, dw, dxs = _pallas_mlp_bwd(
        dys, w, g, u, *(m.astype(xs.dtype) for m in (gate, up, down)),
        tile_expert, tiles, interpret=interpret)
    # padding rows add zeros: ``act`` is weighted by 0 there, and so are
    # ``d g`` and ``d u``
    dgate, dup = _pallas_tgmm(xs, (dg, du), tile_expert, tiles,
                              gate.shape[0], interpret=interpret)
    ddown, = _pallas_tgmm(act, (dys,), tile_expert, tiles, gate.shape[0],
                          interpret=interpret)
    return (dxs, dw.reshape(-1), dgate.astype(gate.dtype),
            dup.astype(up.dtype), ddown.astype(down.dtype), None, None)


expert_mlp.defvjp(_expert_mlp_fwd, _expert_mlp_bwd)


def hvd_moe_relu2_fwd(tile_expert_ref, tiles_ref, x_ref, w_ref, up_ref,
                      down_ref, u_ref, act_ref, y_ref):
    """One row tile through its expert's relu² MLP: times the up matrix,
    the weighted activation ``relu(u)^2 w`` from the float32 accumulator,
    rounded once, times the down matrix (the combine's weighting rides
    there, as in :func:`hvd_moe_mlp_fwd`). ``u`` is kept, rounded, for the
    backward, and the weighted activation for the down matrix's
    gradient."""
    from jax.experimental import pallas as pl

    del tile_expert_ref                    # read by the index maps

    @pl.when(pl.program_id(0) < tiles_ref[0])
    def _multiply():
        u = _dot(x_ref[...], up_ref[0])
        u_ref[...] = u.astype(u_ref.dtype)
        act = (jnp.square(jnp.maximum(u, 0.0))
               * w_ref[...].reshape(-1, 1)).astype(act_ref.dtype)
        act_ref[...] = act
        y_ref[...] = _dot(act, down_ref[0]).astype(y_ref.dtype)


def hvd_moe_relu2_bwd(tile_expert_ref, tiles_ref, dy_ref, w_ref, u_ref,
                      up_ref, down_ref, du_ref, dw_ref, dx_ref):
    """The backward of :func:`hvd_moe_relu2_fwd` for a tile of ``d ys``, in
    float32 on the accumulator ``t = d ys . down^T``: ``d w = rowsum(t
    relu(u)^2)``, written as a row; ``d u = 2 t w relu(u)`` from the kept
    ``u``, rounded for the up matrix's gradient; ``d xs = d u . up^T``."""
    from jax.experimental import pallas as pl

    del tile_expert_ref

    @pl.when(pl.program_id(0) < tiles_ref[0])
    def _multiply():
        t = _dot(dy_ref[...], down_ref[0], transpose_rhs=True)
        relu = jnp.maximum(u_ref[...].astype(jnp.float32), 0.0)
        dw_ref[...] = jnp.sum(t * relu * relu, axis=1)[None, :]
        du = (2.0 * t * w_ref[...].reshape(-1, 1) * relu).astype(
            du_ref.dtype)
        du_ref[...] = du
        dx_ref[...] = _dot(du, up_ref[0], transpose_rhs=True).astype(
            dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_relu2_fwd(xs, w, up, down, tile_expert, tiles, *,
                      interpret: bool):
    d, f = up.shape[1:]
    return _grouped_call(
        hvd_moe_relu2_fwd, (tile_expert, tiles, xs, w, up, down),
        [_row_tiles(d), _row_scalars(), _matrix_of_tile(up),
         _matrix_of_tile(down)],
        [(f, xs.dtype)] * 2 + [(d, xs.dtype)], interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_relu2_bwd(dys, w, u, up, down, tile_expert, tiles, *,
                      interpret: bool):
    d, f = up.shape[1:]
    return _grouped_call(
        hvd_moe_relu2_bwd, (tile_expert, tiles, dys, w, u, up, down),
        [_row_tiles(d), _row_scalars(), _row_tiles(f), _matrix_of_tile(up),
         _matrix_of_tile(down)],
        [(f, dys.dtype), (None, jnp.float32), (d, dys.dtype)],
        interpret=interpret)


def _relu2_fit(d: int, f: int, itemsize: int) -> bool:
    """Whether the relu² calls have room at experts of ``[d, f]``: an
    expert's two matrices, double-buffered, beside (the backward holds
    most) two tiles of ``d`` and two of ``f`` elements a row, twice each,
    and the float32 accumulators: one of ``d`` and three of ``f`` a row."""
    return (4 * d * f * itemsize
            + TILE_ROWS * (2 * (2 * d + 2 * f) * itemsize + (d + 3 * f) * 4)
            ) <= _VMEM_LIMIT * 7 // 8


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def expert_relu2_mlp(xs, w_rows, up, down, tile_expert, tiles,
                     interpret: bool = False):
    """:func:`expert_mlp` for relu² experts (Nemotron-H's): ``w_rows *
    (relu(xs up[e])^2 down[e])`` tile by tile, ``up`` ``[E, D, F]`` and
    ``down`` ``[E, F, D]``, one matrix before the activation where SwiGLU
    has two. One call forward (:func:`hvd_moe_relu2_fwd`), three backward
    (:func:`hvd_moe_relu2_bwd`, and :func:`hvd_moe_tgmm` for the up's
    gradient and for the down's). The rows past the tiles in use are
    neither written nor read, in any result."""
    return _expert_relu2_fwd(xs, w_rows, up, down, tile_expert, tiles,
                             interpret)[0]


def _expert_relu2_fwd(xs, w_rows, up, down, tile_expert, tiles, interpret):
    w = w_rows.reshape(1, -1)
    u, act, ys = _pallas_relu2_fwd(
        xs, w, up.astype(xs.dtype), down.astype(xs.dtype), tile_expert,
        tiles, interpret=interpret)
    return ys, (xs, w, u, act, up, down, tile_expert, tiles)


@jax.named_scope("hvd.moe_experts")
def _expert_relu2_bwd(interpret, res, dys):
    xs, w, u, act, up, down, tile_expert, tiles = res
    dys = dys.astype(xs.dtype)
    du, dw, dxs = _pallas_relu2_bwd(
        dys, w, u, up.astype(xs.dtype), down.astype(xs.dtype), tile_expert,
        tiles, interpret=interpret)
    # padding rows add zeros: ``act`` is weighted by 0 there, and so is
    # ``d u``
    dup, = _pallas_tgmm(xs, (du,), tile_expert, tiles, up.shape[0],
                        interpret=interpret)
    ddown, = _pallas_tgmm(act, (dys,), tile_expert, tiles, up.shape[0],
                          interpret=interpret)
    return (dxs, dw.reshape(-1), dup.astype(up.dtype),
            ddown.astype(down.dtype), None, None)


expert_relu2_mlp.defvjp(_expert_relu2_fwd, _expert_relu2_bwd)


def routed_experts(x, router, gate, up, down, *, top_k: int, first: int = 0,
                   select=None, dtype=None,
                   interpret: Optional[bool] = None,
                   router_kind: str = "softmax", bias=None, route_from=None):
    """One routed-expert layer without dropped tokens, for the experts held
    here: ``x`` ``[T, D]``; ``router`` ``[D, E]`` over all ``E`` routed
    experts; ``gate``, ``up`` ``[count, D, F]`` and ``down`` ``[count, F,
    D]``, the SwiGLU experts ``first … first + count - 1`` (``gate`` None:
    relu² experts, ``relu(x up)^2 down``). Returns ``(y,
    local)``: ``y`` ``[T, D]``, each token's ``sum_j w_j expert_j(x)`` over
    its ``top_k`` experts that are held here (weights normalised over all
    ``top_k``; what the others would add is another holder's to compute),
    and ``local``, a float32 scalar, the assignments that landed here.
    ``select`` (``probabilities [T, E] -> scores [T, E]``) puts another
    choice of experts in the router's place, their weights still the
    router's (:func:`route_top_k`): a measurement hands in scores that
    spread the tokens evenly where the router is untrained, as a block is
    handed its ``attention_fn``. ``router_kind`` and ``bias`` are
    :func:`route_top_k`'s ``kind`` and ``bias``; the router reads
    ``route_from`` ``[T, D_r]`` where it is given (a latent layer's experts
    work on a narrower ``x`` than the router reads), else ``x``.

    The router runs in float32 (``highest`` precision); the assignments to
    held experts are sorted by expert into a buffer of static shape that
    holds every one of them whatever the router does
    (:func:`buffer_rows`), the experts' weighted SwiGLU runs over its
    tiles in use in ``dtype`` (:func:`expert_mlp`: Pallas kernels, a row
    tile times its expert's matrices, with the activation, the token's
    weight and their backwards done in float32 on the tile a product
    holds, where an expert's three matrices fit the kernels' VMEM,
    :func:`_experts_fit`; three separate products and passes over the
    whole buffer between them where they do not), and the weighted rows
    are summed back per token (:func:`_to_tokens`: a kernel that reads the
    rows held here, where the rows are whole lanes wide; the same kernel
    is the transpose of the gather into the buffer). The buffer's rows
    past the tiles in use are never written and never read back: nothing
    is dropped, and the work of the products, of what stands between them
    and of the way back follows the rows the router sent here.

    One chip, no exchange: the caller's tokens are all the tokens.
    ``interpret`` defaults to running the kernels interpreted off TPU."""
    tokens, count, routed = x.shape[0], up.shape[0], router.shape[1]
    dtype = dtype or x.dtype
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if _metrics.enabled():
        _metrics.gauge(
            "moe_rows_budget",
            help="rows of a routed layer's sorted buffer, fixed at trace "
                 "time: every assignment held here and a tile of padding "
                 "an expert").set(buffer_rows(tokens, top_k, count))

    with jax.named_scope("hvd.moe_route"):
        weights, experts = route_top_k(
            x if route_from is None else route_from, router, top_k, select,
            kind=router_kind, bias=bias)
        plan = _plan(experts, first=first, count=count)
        xs = _to_rows(x.astype(dtype), plan, top_k, routed, interpret)
        w_rows = _to_rows(weights.reshape(-1, 1), plan, 1, routed, interpret)
        real = (plan["slot_of_row"] < tokens * top_k)[:, None]
    groups = (plan["tile_expert"], plan["tiles"], interpret)
    relu2 = gate is None
    fit = _relu2_fit if relu2 else _experts_fit
    if fit(*up.shape[1:], jnp.dtype(dtype).itemsize):
        if _metrics.enabled():
            _metrics.gauge(
                "moe_experts_fused",
                help="1 where a routed layer's activation, the combine's "
                     "weighting and their backwards were traced inside "
                     "the grouped products' kernels; absent where they "
                     "ran as passes over the whole buffer").set(1)
        with jax.named_scope("hvd.moe_route"):
            # a padding row of a tile in use is weighted by 0; the rows
            # past the tiles in use hold whatever was there and are read
            # by nothing (``_to_tokens`` reads the rows that have a slot)
            w_rows = jnp.where(real, w_rows, 0).reshape(-1)
        with jax.named_scope("hvd.moe_experts"):
            ys = (expert_relu2_mlp(xs, w_rows, up, down, *groups) if relu2
                  else expert_mlp(xs, w_rows, gate, up, down, *groups))
    else:
        with jax.named_scope("hvd.moe_experts"):
            if relu2:
                act = jnp.square(jax.nn.relu(grouped_matmul(xs, up, *groups)))
            else:
                act = (jax.nn.silu(grouped_matmul(xs, gate, *groups))
                       * grouped_matmul(xs, up, *groups))
            ys = grouped_matmul(act, down, *groups)
        with jax.named_scope("hvd.moe_route"):
            # chosen away, never multiplied: ``0 x NaN``
            ys = (jnp.where(real, ys.astype(jnp.float32), 0) * w_rows
                  ).astype(dtype)
    with jax.named_scope("hvd.moe_route"):
        y = _to_tokens(ys, plan, top_k, routed, interpret)
    return y, plan["local"].astype(jnp.float32)


def record_rows(batch_stats) -> float:
    """Sum the routed blocks' ``moe_rows`` counters of a step's
    ``batch_stats`` (``models.TransformerLM`` keeps each routed block's
    last ``local`` there) and set the gauge ``moe_local_rows``. It reads
    the device: call it where the loop already waits for a step (a logging
    interval, after the checked steps), not every step."""
    leaves = [v for path, v in jax.tree_util.tree_leaves_with_path(
        batch_stats) if getattr(path[-1], "key", None) == "moe_rows"]
    total = float(jax.device_get(sum(leaves))) if leaves else 0.0
    if _metrics.enabled():
        _metrics.gauge(
            "moe_local_rows",
            help="assignments to experts held here in the last step read, "
                 "summed over the routed layers: what the grouped "
                 "products' time follows").set(total)
    return total
