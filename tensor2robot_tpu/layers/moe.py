"""Flax wrapper over the expert-parallel MoE op (ops/moe.py).

`MoEBlock` drops in where a dense MLP would sit (e.g. the feed-forward of
layers/transformer.TransformerBlock): [batch, seq, features] in and out,
plus the router's load-balance aux loss, which callers fold into the
training loss (weight ~1e-2, the Switch Transformer default).
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.ops import moe as moe_ops


class MoEBlock(nn.Module):
    """Top-k routed expert MLP over [batch, seq, features]."""

    num_experts: int
    hidden_dim: int
    num_selected: int = 2
    capacity_factor: float = 2.0
    group_size: Optional[int] = None  # default: one group per batch element
    mesh: Optional[object] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        batch, seq, features = x.shape
        router_kernel = self.param(
            "router",
            nn.initializers.lecun_normal(),
            (features, self.num_experts),
        )
        w_in = self.param(
            "w_in",
            nn.initializers.lecun_normal(),
            (self.num_experts, features, self.hidden_dim),
        )
        w_out = self.param(
            "w_out",
            nn.initializers.lecun_normal(),
            (self.num_experts, self.hidden_dim, features),
        )
        y, aux_loss = moe_ops.moe_mlp(
            x.reshape(batch * seq, features),
            router_kernel,
            w_in,
            w_out,
            num_selected=self.num_selected,
            capacity_factor=self.capacity_factor,
            # Per-batch-element routing groups keep dispatch linear in
            # batch size (ops/moe.py group_size doc).
            group_size=self.group_size or seq,
            mesh=self.mesh,
        )
        return y.reshape(batch, seq, features), aux_loss


class RoutedExperts(nn.Module):
    """One chip's share of a sigmoid-routed expert layer with a shared
    expert (`ops/moe.routed_experts`), over [batch, seq, features]:

        y = sum_{e in top-k, held} w_e E_e(x) + E_shared(x)

    every expert a SwiGLU of width `hidden_dim`. The router scores all
    `router_experts`; this layer holds the `num_experts` from
    `first_expert` on and leaves out what the others would add (another
    chip's part, never computed here). The selection bias is zero at the
    start and gets no gradient: it is a parameter so that a checkpoint
    carries it.

    Returns (y, counts) with counts float32 [4] (`COUNT_NAMES`): pairs
    routed to held experts, the fullest held expert's rows, those times the
    experts held (what a layout sized for the fullest would compute: over
    the routed rows it is the imbalance), positions routed.
    """

    num_experts: int           # held here
    router_experts: int        # scored by the router
    hidden_dim: int
    num_selected: int
    first_expert: int = 0
    shared_experts: int = 1
    scaling: float = 1.0
    dtype: Optional[jnp.dtype] = None

    COUNT_NAMES = (
        "moe_routed_rows", "moe_max_expert_rows", "moe_peak_rows",
        "moe_positions",
    )

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        from tensor2robot_tpu.layers.transformer import SwiGLU

        batch, seq, features = x.shape
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (features, self.router_experts))
        bias = self.param(
            "selection_bias", nn.initializers.zeros, (self.router_experts,)
        )
        matrices = {
            name: self.param(name, init, shape)
            for name, shape in (
                ("gate", (self.num_experts, features, self.hidden_dim)),
                ("up", (self.num_experts, features, self.hidden_dim)),
                ("down", (self.num_experts, self.hidden_dim, features)),
            )
        }
        y, counts = moe_ops.routed_experts(
            x.reshape(batch * seq, features), router, bias,
            matrices["gate"], matrices["up"], matrices["down"],
            held=(self.first_expert, self.num_experts),
            num_selected=self.num_selected, scaling=self.scaling,
        )
        y = y.reshape(batch, seq, features).astype(x.dtype)
        if self.shared_experts:
            y = y + SwiGLU(
                self.shared_experts * self.hidden_dim, dtype=self.dtype,
                kernel_init=init, scope_name="moe/shared", name="shared",
            )(x)
        fullest = counts["max_expert_rows"].astype(jnp.float32)
        return y, jnp.stack([
            counts["routed_rows"].astype(jnp.float32), fullest,
            fullest * self.num_experts, jnp.float32(batch * seq),
        ])
