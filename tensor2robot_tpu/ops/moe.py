"""Mixture-of-Experts with expert parallelism (GShard-style dense dispatch).

Beyond the reference (SURVEY §2.7 lists expert parallelism as ABSENT
there): a top-k routed expert MLP whose dispatch/combine are dense einsums
over a [tokens, experts, capacity] one-hot tensor — the TPU-native MoE
formulation (GShard / Switch Transformer): static shapes, no gather/
scatter, everything lands on the MXU, and when the expert dimension of the
weights is sharded over the `expert` mesh axis GSPMD lowers the dispatch
einsum to an all_to_all over ICI. Tokens beyond an expert's capacity are
dropped (contribute zero), the standard capacity-factor contract.

`routed_experts` is the other routing: no capacity and no dropped token.
A layer is told which experts it holds (`held`), scores and chooses over all
of them, sorts the (token, choice) pairs routed to its own by expert and
runs one grouped product a matrix over the sorted rows. That is what one
chip of an expert-parallel deployment computes, without the exchange. Rows
go out to the experts through the sorted order and come back to their
tokens through its inverse, both as gathers, forward and backward: the
layer holds no scatter.

Pure functions here; `layers.moe.MoEBlock` and `layers.moe.RoutedExperts`
are the flax wrappers.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from tensor2robot_tpu.parallel.mesh import EXPERT_AXIS


class Routing(NamedTuple):
    """Dense dispatch/combine for [T] tokens, [E] experts, [C] capacity."""

    dispatch: jax.Array  # [T, E, C] 0/1 — token t occupies slot c of expert e
    combine: jax.Array  # [T, E, C] gate-weighted dispatch
    aux_loss: jax.Array  # scalar load-balance loss (Switch eq. 4 style)


def top_k_routing(
    router_logits: jax.Array,
    num_selected: int,
    capacity: int,
) -> Routing:
    """Builds dispatch/combine tensors from router logits [T, E].

    Top-k gating with renormalized softmax gates; per-expert slots assigned
    in token order (cumsum ranking); tokens ranked past `capacity` are
    dropped. The aux loss is E * sum_e(load_e * importance_e) where load is
    the fraction of top-1 assignments and importance the mean router
    probability — minimized by uniform routing.
    """
    tokens, num_experts = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_values, expert_ids = jax.lax.top_k(probs, num_selected)
    if num_selected > 1:
        # Renormalize the selected gates so they sum to 1 per token.
        gate_values = gate_values / jnp.maximum(
            jnp.sum(gate_values, axis=-1, keepdims=True), 1e-9
        )
    # Top-1 keeps the RAW probability as the gate (Switch Transformer):
    # renormalizing would pin it to 1.0 and cut the router out of the task
    # loss's gradient entirely.

    dispatch = jnp.zeros((tokens, num_experts, capacity), probs.dtype)
    combine = jnp.zeros((tokens, num_experts, capacity), probs.dtype)
    # Slots fill selection-major: all k=0 picks rank before any k=1 pick,
    # so a token's primary expert wins capacity over another's secondary.
    slots_used = jnp.zeros((num_experts,), jnp.int32)
    for k in range(num_selected):
        onehot = jax.nn.one_hot(
            expert_ids[:, k], num_experts, dtype=jnp.int32
        )  # [T, E]
        rank = jnp.cumsum(onehot, axis=0) - 1 + slots_used[None, :]  # [T, E]
        position = jnp.sum(rank * onehot, axis=1)  # [T] slot within expert
        kept = position < capacity
        # slots_used counts KEPT assignments, so it is a true slots-filled
        # count (saturates at capacity). Note this does not change which
        # tokens are kept vs the naive all-assignments count: a round can
        # only drop once the expert is full, and a full expert drops every
        # later-k candidate under either accounting.
        slots_used = slots_used + jnp.sum(onehot * kept[:, None], axis=0)
        slot_onehot = jax.nn.one_hot(position, capacity, dtype=probs.dtype)
        contribution = (
            onehot.astype(probs.dtype)[:, :, None] * slot_onehot[:, None, :]
        )
        contribution = contribution * kept.astype(probs.dtype)[:, None, None]
        dispatch = dispatch + contribution
        combine = combine + contribution * gate_values[:, k][:, None, None]

    # Load-balance: fraction of tokens whose TOP-1 pick is e, dotted with
    # mean router prob for e, scaled by E (1.0 at perfect uniformity).
    top1 = jax.nn.one_hot(expert_ids[:, 0], num_experts, dtype=probs.dtype)
    load = jnp.mean(top1, axis=0)
    importance = jnp.mean(probs, axis=0)
    aux_loss = num_experts * jnp.sum(load * importance)
    return Routing(dispatch=dispatch, combine=combine, aux_loss=aux_loss)


def expert_capacity(
    tokens: int,
    num_experts: int,
    num_selected: int,
    capacity_factor: float,
) -> int:
    """Slots per expert: ceil(k*T/E * factor), floored at num_selected so
    toy shapes keep at least one slot per selection."""
    raw = num_selected * tokens * capacity_factor / num_experts
    return max(int(-(-raw // 1)), num_selected)


def moe_mlp(
    x: jax.Array,
    router_kernel: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    num_selected: int = 2,
    capacity_factor: float = 2.0,
    group_size: Optional[int] = None,
    mesh: Optional[object] = None,
):
    """Expert-routed MLP over [T, F] tokens.

    Args:
      x: [T, F] tokens (flatten batch/seq upstream).
      router_kernel: [F, E].
      w_in: [E, F, H] per-expert up-projection; w_out: [E, H, F].
      group_size: tokens are routed in independent groups of this size
        (must divide T), with capacity computed PER GROUP — the GShard
        grouping that keeps the dense dispatch tensors linear in T
        ([G, g, E, C_g] with C_g ∝ g/E) instead of quadratic (a single
        global group's capacity grows with T, making [T, E, C] ~ T^2).
        None = one global group (fine for small T).
      mesh: when given with an `expert` axis > 1, expert-dim sharding
        constraints are applied so GSPMD inserts the token all_to_all and
        each device computes only its resident experts' FFNs.

    Returns (y [T, F], aux_loss scalar — mean over groups).
    """
    tokens, features = x.shape
    num_experts = w_in.shape[0]
    if group_size is None:
        group_size = tokens
    if tokens % group_size != 0:
        raise ValueError(
            f"group_size {group_size} does not divide token count {tokens}"
        )
    groups = tokens // group_size
    capacity = expert_capacity(
        group_size, num_experts, num_selected, capacity_factor
    )

    xg = x.reshape(groups, group_size, features)
    logits = jnp.einsum("gtf,fe->gte", xg, router_kernel)
    routing = jax.vmap(
        lambda lg: top_k_routing(lg, num_selected, capacity)
    )(logits)

    expert_inputs = jnp.einsum("gtec,gtf->gecf", routing.dispatch, xg)
    if mesh is not None and dict(mesh.shape).get(EXPERT_AXIS, 1) > 1:
        expert_inputs = jax.lax.with_sharding_constraint(
            expert_inputs,
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, EXPERT_AXIS)
            ),
        )
    hidden = jax.nn.gelu(jnp.einsum("gecf,efh->gech", expert_inputs, w_in))
    expert_outputs = jnp.einsum("gech,ehf->gecf", hidden, w_out)
    y = jnp.einsum("gtec,gecf->gtf", routing.combine, expert_outputs)
    return y.reshape(tokens, features), jnp.mean(routing.aux_loss)


def sigmoid_top_k(x, router_kernel, selection_bias, num_selected, scaling):
    """(ids [T, k], weights [T, k]) of a sigmoid router with a selection-only
    bias: `s = sigmoid(x W_r)` in float32, the k experts with the largest `s
    + bias`, weights `scaling * s / sum of the chosen s` (the bias chooses
    and does not weigh). The chosen scores are read through a one-hot, whose
    transpose is a product and not a scatter."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, ids = jax.lax.top_k(scores + selection_bias.astype(jnp.float32), num_selected)
    chosen = jnp.sum(
        scores[:, None, :] * jax.nn.one_hot(ids, scores.shape[-1], dtype=scores.dtype),
        axis=-1,
    )
    return ids, scaling * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def default_row_buffer(tokens, num_selected, held_count, router_experts):
    """Rows of `routed_experts`' static buffer: four times what an even
    router sends to the experts held, in whole tiles of 256. A share's
    router trains toward the experts it holds (their outputs alone reach its
    loss): two to two and a half times the even load is what a step of 16k
    tokens reads after a few updates, single layers three times, and a
    buffer that such a step overflows makes the step's time jump by a slab
    with the routing."""
    even = tokens * num_selected * held_count / router_experts
    return min(256 * math.ceil(4 * even / 256), tokens * num_selected)


def routed_experts(
    x: jax.Array,
    router_kernel: jax.Array,
    selection_bias: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    held: Tuple[int, int],
    num_selected: int,
    scaling: float = 1.0,
    row_buffer: Optional[int] = None,
    expert_counts: bool = False,
):
    """This chip's share of `sum_{e in top-k} w_e SwiGLU_e(x)` over [T, F]
    tokens: the sum runs over the chosen experts that are held, the weights
    are normalised over all k chosen, held or not.

    Args:
      router_kernel [F, E_all], selection_bias [E_all]: the whole router.
      w_gate, w_up [E, F, H], w_down [E, H, F]: experts `first .. first + E`.
      held: (first, E).
      row_buffer: rows of the sorted pairs a grouped product takes at once
        (None: `default_row_buffer`). No token is dropped whatever the
        imbalance: pairs beyond the buffer go through it in further slabs,
        which a step with near-even routing never enters.
      expert_counts: also count the pairs routed to each of the router's
        outputs, held or not (what `balance_bias_by_counts` hands on).

    Returns (y [T, F] float32, {"routed_rows": pairs routed to held experts,
    "max_expert_rows": the fullest held expert's, and with `expert_counts`
    "expert_counts": float32 [E_all]}).

    Rows go out to the experts through the sorted order (`order[r]`: the
    pair at row r) and come back to their tokens through its inverse
    (`pos[t, j]`: the row of token t's j-th choice), both as gathers, forward
    and backward: the layer holds no scatter. `pos` and the experts' sizes
    are ranks and counts from a one-hot of the pairs' experts.
    """
    tokens, features = x.shape
    first, count = held
    if w_gate.shape[0] != count:
        raise ValueError(f"{w_gate.shape[0]} expert matrices for {count} held")
    pairs = tokens * num_selected
    rows = row_buffer or default_row_buffer(
        tokens, num_selected, count, router_kernel.shape[1]
    )
    slabs = -(-pairs // rows)

    with jax.named_scope("moe/router"):
        ids, weights = sigmoid_top_k(
            x, router_kernel, selection_bias, num_selected, scaling
        )
    with jax.named_scope("moe/dispatch"):
        local = ids - first
        # Held pairs first, by expert; the others behind them.
        key = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(key.reshape(pairs), stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, slabs * rows - pairs))
        pos, sizes = _sorted_positions(key, count + 1)
        sizes = sizes[:count]
    y = _sorted_experts(rows, x, weights, w_gate, w_up, w_down, order, pos, sizes)
    counts = {"routed_rows": jnp.sum(sizes), "max_expert_rows": jnp.max(sizes)}
    if expert_counts:
        with jax.named_scope("moe/bias_update"):
            counts["expert_counts"] = jnp.sum(
                jax.nn.one_hot(ids, router_kernel.shape[1], dtype=jnp.float32),
                axis=(0, 1),
            )
    return y, counts


def _sorted_positions(key, buckets):
    """(pos [T, k] int32, sizes [buckets] int32) of `key` [T, k]: `pos[t, j]`
    is where pair t * k + j lands in the stable sort of the flattened keys,
    the inverse of that sort's order, counted without a scatter: the pairs of
    a bucket before it are those of earlier tokens (a cumulative sum over
    the tokens of a one-hot) and those of its own token's earlier choices."""
    hot = (key[..., None] == jnp.arange(buckets)).astype(jnp.int32)   # [T, k, B]
    per_token = jnp.sum(hot, axis=1)
    sizes = jnp.sum(per_token, axis=0)
    before = (jnp.cumsum(sizes) - sizes) + jnp.cumsum(per_token, axis=0) - per_token
    within = jnp.cumsum(hot, axis=1) - hot
    return jnp.sum(hot * (before[:, None, :] + within), axis=-1), sizes


@jax.custom_vjp
def balance_bias_by_counts(y, selection_bias, counts):
    """y, unchanged; in the backward pass the selection bias's gradient is
    `counts`, the step's pairs routed to each router output, and nothing of
    y's cotangent.

    The bias only chooses experts, so the loss gives it no gradient; the
    load-balancing rule that moves it (`models/optimizers.
    with_selection_bias_update`) needs the step's counts instead, and the
    gradient is the one slot by which a value of the step reaches the
    optimizer. y is the layer's output, whose cotangent is always computed,
    so that this backward rule always runs."""
    del selection_bias, counts
    return y


def _balance_fwd(y, selection_bias, counts):
    del selection_bias
    return y, counts


def _balance_bwd(counts, g):
    return g, counts, jnp.zeros_like(counts)


balance_bias_by_counts.defvjp(_balance_fwd, _balance_bwd)


def _dispatch(rows, start, x, order, sizes, num_selected):
    """Rows [start, start + rows) of the sorted pairs: (pair [rows], token
    [rows], group sizes [E] of the slab, valid [rows, 1], taken [rows, F])."""
    ends = jnp.cumsum(sizes)
    with jax.named_scope("moe/dispatch"):
        pair = jax.lax.dynamic_slice(order, (start,), (rows,))
        token = pair // num_selected
        group = jnp.clip(ends - start, 0, rows) - jnp.clip(
            ends - sizes - start, 0, rows
        )
        # A grouped product leaves the rows behind its last group undefined,
        # forward and backward: they are zeroed on the way in (so that no
        # gradient comes back through them) and on the way out.
        valid = ((start + jnp.arange(rows)) < ends[-1])[:, None]
        return pair, token, group, valid, jnp.where(valid, x[token], 0)


def _experts(group, valid, taken, w_gate, w_up, w_down):
    """The slab's rows through their experts' SwiGLU, float32 [rows, F], the
    rows behind the last group zeroed."""
    with jax.named_scope("moe/experts"):
        def grouped(a, w):
            return jax.lax.ragged_dot(
                a, w.astype(a.dtype), group,
                precision=(jax.lax.Precision.HIGHEST
                           if a.dtype == jnp.float32 else None),
                preferred_element_type=jnp.float32,
            )

        hidden = (
            jax.nn.silu(grouped(taken, w_gate)) * grouped(taken, w_up)
        ).astype(taken.dtype)
        out = grouped(hidden, w_down)
    with jax.named_scope("moe/combine"):
        # Zeroed before the weights meet them: the weights' gradient is each
        # row times its cotangent, and 0 times an undefined row need not be 0.
        return jnp.where(valid, out, 0.0)


def _rows_of(pos, start, rows, routed):
    """(row [T, k] within the slab, clipped into it; held [T, k]: the pair's
    row is in the slab and in front of `routed`)."""
    at = pos - start
    return jnp.clip(at, 0, rows - 1), (at >= 0) & (at < rows) & (pos < routed)


#: Rows a step of `_to_tokens`' loop gathers: one block of the sorted tokens.
TOKEN_BLOCK = 1024


def _to_tokens(values, row, held, weights):
    """float32 [T, F]: `sum_j weights[t, j] * values[row[t, j]]` over the
    choices `held`, by gathers whose rows follow the pairs held, not T x k.

    The tokens are ordered by how many of their choices are held, most
    first, so that those with an i-th held choice are the first n_i; the
    i-th held choices go a block of `TOKEN_BLOCK` of those tokens at a time,
    each gathered block added to its own block of the sorted tokens (no two
    rows of a block meet), and one gather of [T, F] puts the tokens back in
    their order."""
    tokens, k = row.shape
    block = min(TOKEN_BLOCK, tokens)
    padded = -(-tokens // block) * block
    count = jnp.sum(held, axis=1, dtype=jnp.int32)
    # The i-th held choice of a token: its row and weight, [T, i].
    nth = held[:, :, None] & (
        (jnp.cumsum(held, axis=1, dtype=jnp.int32) - 1)[:, :, None] == jnp.arange(k))
    level_row = jnp.sum(jnp.where(nth, row[:, :, None], 0), axis=1)
    level_weight = jnp.sum(jnp.where(nth, weights[:, :, None], 0.0), axis=1)
    place, sizes = _sorted_positions((k - count)[:, None], k + 1)
    first = jnp.argsort(k - count, stable=True)
    # Tokens with more than i held choices, and the blocks each level takes.
    reach = jnp.cumsum(sizes)[k - 1 - jnp.arange(k)]
    blocks = jnp.cumsum(-(-reach // block))
    pad = lambda a: jnp.pad(a[first].T, ((0, 0), (0, padded - tokens)))
    sources, scale = pad(level_row), pad(level_weight)

    def body(carry):
        step, acc = carry
        level = jnp.sum(blocks <= step)
        at = (step - jnp.where(level > 0, blocks[level - 1], 0)) * block
        got = values[jax.lax.dynamic_slice(sources, (level, at), (1, block))[0]]
        got = got.astype(jnp.float32) * jax.lax.dynamic_slice(
            scale, (level, at), (1, block))[0][:, None]
        got = jnp.where(((at + jnp.arange(block)) < reach[level])[:, None], got, 0.0)
        return step + 1, jax.lax.dynamic_update_slice(
            acc, jax.lax.dynamic_slice(acc, (at, 0), got.shape) + got, (at, 0))

    _, acc = jax.lax.while_loop(
        lambda carry: carry[0] < blocks[-1], body,
        (jnp.int32(0), jnp.zeros((padded, values.shape[1]), jnp.float32)),
    )
    return acc[place[:, 0]]


def _slab(rows, start, x, weights, w_gate, w_up, w_down, order, pos, sizes):
    """Rows [start, start + rows) of the sorted pairs through their experts,
    weighted and gathered back to their tokens, float32 [T, F]."""
    _, _, group, valid, taken = _dispatch(
        rows, start, x, order, sizes, pos.shape[1])
    out = _experts(group, valid, taken, w_gate, w_up, w_down)
    with jax.named_scope("moe/combine"):
        return _to_tokens(out, *_rows_of(pos, start, rows, jnp.sum(sizes)), weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sorted_experts(rows, x, weights, w_gate, w_up, w_down, order, pos, sizes):
    """sum of `_slab` over as many slabs of `rows` sorted pairs as the
    routing filled: a loop whose trip count the data decides, forward and
    backward, so that a step computes the rows it routed and no more, and
    holds one slab's intermediates whatever their number. The backward is
    written out: each gather's transpose is a gather the other way (through
    `pos` where the forward read through `order`, and back), so that no
    scatter-add is left for autodiff to make; the products' own transposes
    are jax's."""
    routed = jnp.sum(sizes)

    def body(carry):
        start, y = carry
        return start + rows, y + _slab(
            rows, start, x, weights, w_gate, w_up, w_down, order, pos, sizes)

    return jax.lax.while_loop(
        lambda carry: carry[0] < routed, body,
        (jnp.int32(0), jnp.zeros(x.shape, jnp.float32)),
    )[1]


def _sorted_experts_fwd(rows, *operands):
    return _sorted_experts(rows, *operands), operands


def _sorted_experts_bwd(rows, operands, dy):
    x, weights, w_gate, w_up, w_down, order, pos, sizes = operands
    floats = (x, weights, w_gate, w_up, w_down)
    routed = jnp.sum(sizes)
    pair_weights = weights.reshape(-1)

    def body(carry):
        start, (dx, dw, *d_matrices) = carry
        pair, token, group, valid, taken = _dispatch(
            rows, start, x, order, sizes, pos.shape[1])
        out, pull = jax.vjp(
            functools.partial(_experts, group, valid), taken, w_gate, w_up, w_down)
        with jax.named_scope("moe/combine"):
            row, held = _rows_of(pos, start, rows, routed)
            d_rows = dy[token]
            dw = dw + jnp.where(held, jnp.sum(d_rows * out, axis=-1)[row], 0.0)
            d_out = jnp.where(valid, d_rows * pair_weights[pair][:, None], 0.0)
        d_taken, *d_slab = pull(d_out)
        with jax.named_scope("moe/dispatch"):
            dx = dx + _to_tokens(d_taken, row, held, jnp.ones(held.shape, jnp.float32))
        return start + rows, (dx, dw) + tuple(
            g + d.astype(g.dtype) for g, d in zip(d_matrices, d_slab))

    _, grads = jax.lax.while_loop(
        lambda carry: carry[0] < routed, body,
        (jnp.int32(0), tuple(jnp.zeros(f.shape, jnp.float32) for f in floats)),
    )
    return tuple(g.astype(f.dtype) for g, f in zip(grads, floats)) + (None,) * 3


_sorted_experts.defvjp(_sorted_experts_fwd, _sorted_experts_bwd)
