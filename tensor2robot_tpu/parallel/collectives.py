"""The gradient-collective registry: quantized reduce-scatter/all-gather.

After PR's cross-replica weight-update sharding (ZeRO-2, arXiv:2004.13336)
the per-step cost on the data axis is COMMS, not FLOPs: every step moves
the full fp32 gradient through a reduce-scatter and the full update back
through an all-gather. EQuARX (arXiv:2506.17615) shows blockwise-quantized
all-reduce recovers most of that bandwidth at negligible quality cost.
This module is the single home for that machinery:

  * a registry of `GradientCollective`s — `none` (exact fp32, lowering to
    the same psum_scatter/all_gather GSPMD emits), `fp16`, `int8`,
    `fp8_e4m3` and `fp8_e5m2` (blockwise per-block scales) — selected by
    the central `T2R_COLLECTIVE_QUANT` / `T2R_COLLECTIVE_BLOCK` flags;
  * error feedback: both quantized collectives return the dequantized
    copy of what was actually transmitted, so the caller can carry
    `sent - intended` as a residual and re-inject it next step (the
    EF-SGD contract that preserves convergence under biased compression);
  * `FlatShardLayout`: the pad-to-block bookkeeping that maps a raveled
    gradient vector onto equal per-device shards;
  * the SANCTIONED spellings of jax's manual collectives (`psum`,
    `pmean`, `ppermute`, `all_to_all`, `all_gather`, `psum_scatter`,
    `axis_index`) and of `shard_map` itself. The
    `collective-outside-registry` lint (analysis/lints.py) errors on raw
    `jax.lax.p*` / `shard_map` use anywhere else in `train/` and
    `parallel/`, so every byte that crosses the data axis is visible —
    and quantizable — from this one file.

Wire-format accounting is analytic (`wire_bytes`): XLA does not expose
per-collective byte counters, but the payload is exactly the arrays we
hand to `all_to_all`/`all_gather`, so bytes = sum of payload sizes.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map

from tensor2robot_tpu import flags

__all__ = [
    "GradientCollective",
    "FlatShardLayout",
    "available_collectives",
    "get_collective",
    "register_collective",
    "smap",
    "wire_summary",
    # sanctioned manual-collective spellings (lint: collective-outside-
    # registry bans the raw jax.lax forms outside this file):
    "all_gather",
    "all_to_all",
    "axis_index",
    "pmean",
    "ppermute",
    "psum",
    "psum_scatter",
    "shard_map",
]


# -- sanctioned primitive spellings -------------------------------------------
# Thin passthroughs, not abstractions: their value is that every manual
# collective in train/ + parallel/ routes through ONE importable, greppable,
# lintable module. They accept pytrees wherever jax.lax does.

# `shard_map` itself is jax's, re-exported so manual-collective programs
# import it from the one lintable module.


def smap(fn, mesh, in_specs, out_specs, check_vma: bool = False):
    """`shard_map` with the trainer's defaults (replication checking off:
    the quantized update produces replicated outputs by construction —
    psum'd metrics, identically-computed params — which the static
    checker cannot always prove through all_to_all/gather chains)."""
    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def psum(x, axis_name):
    return lax.psum(x, axis_name)


def pmean(x, axis_name):
    return lax.pmean(x, axis_name)


def ppermute(x, axis_name, perm):
    return lax.ppermute(x, axis_name, perm=perm)


def all_to_all(x, axis_name, split_axis, concat_axis, *, tiled=False):
    return lax.all_to_all(
        x, axis_name, split_axis, concat_axis, tiled=tiled
    )


def all_gather(x, axis_name, *, axis=0, tiled=False):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def psum_scatter(x, axis_name, *, scatter_dimension=0, tiled=False):
    return lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled
    )


def axis_index(axis_name):
    return lax.axis_index(axis_name)


# -- blockwise quantization ----------------------------------------------------


def _block_view(x: jax.Array, block: int) -> jax.Array:
    """[..., L] -> [..., L//block, block]; L must divide by block (the
    FlatShardLayout guarantees it for trainer payloads)."""
    if x.shape[-1] % block != 0:
        raise ValueError(
            f"last dim {x.shape[-1]} not divisible by block {block}"
        )
    return x.reshape(x.shape[:-1] + (x.shape[-1] // block, block))


def _block_scales(blocks: jax.Array) -> jax.Array:
    """Per-block max-abs scale with zero blocks mapped to scale 1 (their
    quantized payload is all zeros either way; 1 keeps decode NaN-free)."""
    scale = jnp.max(jnp.abs(blocks), axis=-1)
    return jnp.where(scale > 0, scale, jnp.ones_like(scale))


@dataclasses.dataclass(frozen=True)
class GradientCollective:
    """One wire format for the data-axis gradient collectives.

    encode/decode are exact inverses of the TRANSMITTED value (not of the
    input): `decode(encode(x))` is the dequantized copy the receivers
    reconstruct, and `x - decode(encode(x))` is the error-feedback
    residual. Subclasses override `encode`/`decode`/`bits` (and may
    override the collectives themselves — the exact path uses the fused
    psum_scatter lowering instead of quantize+all_to_all).
    """

    name: str
    block: int

    # - wire format -
    def encode(self, x: jax.Array):
        raise NotImplementedError

    def decode(self, payload) -> jax.Array:
        raise NotImplementedError

    def wire_bytes(self, n_elements: int) -> int:
        """Payload bytes for n fp32 elements (values + per-block scales)."""
        raise NotImplementedError

    # - collectives -
    def reduce_scatter(
        self, rows: jax.Array, axis_name: str
    ) -> Tuple[jax.Array, jax.Array]:
        """Quantized reduce-scatter over `axis_name`.

        `rows` is the device's local gradient split into one [L] chunk
        per peer: shape [N, L] with N the axis size. Chunk j is encoded
        and shipped to peer j (all_to_all); each device decodes the N
        chunks it receives and sums them exactly in fp32.

        Returns (reduced [L], sent [N, L]): `reduced` is this device's
        shard of the SUM over peers of their dequantized chunks; `sent`
        is the dequantized copy of what this device transmitted —
        `rows - sent` is the error-feedback residual.
        """
        payload = self.encode(rows)
        received = jax.tree_util.tree_map(
            lambda t: all_to_all(t, axis_name, 0, 0, tiled=True), payload
        )
        reduced = self.decode(received).astype(jnp.float32).sum(axis=0)
        return reduced, self.decode(payload).astype(jnp.float32)

    def all_gather_shard(
        self, shard: jax.Array, axis_name: str
    ) -> Tuple[jax.Array, jax.Array]:
        """Quantized all-gather of a per-device [L] shard.

        Returns (full [N*L], sent [L]): `full` concatenates every peer's
        dequantized shard in axis order (identical on all devices);
        `sent` is the dequantized copy of this device's own contribution
        — `shard - sent` is the error-feedback residual.
        """
        payload = self.encode(shard)
        gathered = jax.tree_util.tree_map(
            lambda t: all_gather(t, axis_name, tiled=True), payload
        )
        full = self.decode(gathered).astype(jnp.float32)
        return full, self.decode(payload).astype(jnp.float32)


class ExactCollective(GradientCollective):
    """fp32 passthrough: byte-for-byte the collectives GSPMD emits for the
    ZeRO-2 step (psum_scatter + all_gather), with a no-op error channel."""

    def encode(self, x):
        return {"v": x}

    def decode(self, payload):
        return payload["v"]

    def wire_bytes(self, n_elements: int) -> int:
        return 4 * n_elements

    def reduce_scatter(self, rows, axis_name):
        reduced = psum_scatter(rows, axis_name, scatter_dimension=0)
        return reduced, rows

    def all_gather_shard(self, shard, axis_name):
        return all_gather(shard, axis_name, tiled=True), shard


class BlockScaledCollective(GradientCollective):
    """Shared decode for the `{'q': values, 's': per-block scales}` wire
    format: cast to fp32, multiply each block by its scale. One body so
    the two quantized formats cannot silently diverge."""

    def decode(self, payload):
        q, scales = payload["q"], payload["s"]
        blocks = _block_view(q.astype(jnp.float32), self.block)
        return (blocks * scales[..., None]).reshape(q.shape)


class Fp16Collective(BlockScaledCollective):
    """Blockwise-scaled fp16: each block is normalized by its max-abs to
    [-1, 1] before the cast, so no block can overflow fp16 range and small
    blocks keep full relative precision. 2 bytes/element + 4/block."""

    def encode(self, x):
        blocks = _block_view(x, self.block)
        scales = _block_scales(blocks)
        values = (blocks / scales[..., None]).astype(jnp.float16)
        return {"q": values.reshape(x.shape), "s": scales}

    def wire_bytes(self, n_elements: int) -> int:
        return 2 * n_elements + 4 * (n_elements // self.block)


class Int8Collective(BlockScaledCollective):
    """Blockwise symmetric int8: scale = max|block| / 127, round-to-
    nearest. 1 byte/element + 4/block — 3.94x fewer wire bytes than fp32
    at the default block of 512."""

    def encode(self, x):
        blocks = _block_view(x, self.block)
        scales = _block_scales(blocks) / 127.0
        values = jnp.clip(
            jnp.round(blocks / scales[..., None]), -127, 127
        ).astype(jnp.int8)
        return {"q": values.reshape(x.shape), "s": scales}

    def wire_bytes(self, n_elements: int) -> int:
        return n_elements + 4 * (n_elements // self.block)


class Fp8Collective(BlockScaledCollective):
    """Blockwise-scaled fp8: each block is normalized so its max-abs maps
    to the format's largest finite value (the full exponent range earns
    its keep, unlike a [-1, 1] normalization), clipped, then cast. The
    clip is load-bearing: jax fp8 casts do NOT saturate — an overflow
    becomes NaN, and one NaN would poison the whole reduced shard. Same
    wire cost as int8 (1 byte/element + 4/block); the trade is rounding
    that is RELATIVE per element (floating mantissa) instead of absolute
    per block, which favors gradients whose blocks mix magnitudes.
    `decode` is the shared BlockScaledCollective body — fp8 payloads are
    bit-compatible with the rest of the registry's q/s wire format.
    """

    _DTYPE = None  # subclass: the ml_dtypes fp8 storage dtype
    _MAX = 0.0  # subclass: largest finite value of the format

    def encode(self, x):
        blocks = _block_view(x, self.block)
        scales = _block_scales(blocks) / self._MAX
        values = jnp.clip(
            blocks / scales[..., None], -self._MAX, self._MAX
        ).astype(self._DTYPE)
        return {"q": values.reshape(x.shape), "s": scales}

    def wire_bytes(self, n_elements: int) -> int:
        return n_elements + 4 * (n_elements // self.block)


class Fp8E4M3Collective(Fp8Collective):
    """fp8 e4m3 (3 mantissa bits, max 448): ~2^-4 relative rounding —
    the precision-leaning fp8 format."""

    _DTYPE = jnp.float8_e4m3fn
    _MAX = 448.0


class Fp8E5M2Collective(Fp8Collective):
    """fp8 e5m2 (2 mantissa bits, max 57344): ~2^-3 relative rounding —
    the range-leaning fp8 format (bfloat16's dynamic range, halved)."""

    _DTYPE = jnp.float8_e5m2
    _MAX = 57344.0


# -- the registry --------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[int], GradientCollective]] = {}


def register_collective(name: str):
    """Registers a factory(block) -> GradientCollective under `name`."""

    def deco(factory):
        if name in _REGISTRY:
            raise ValueError(f"collective {name!r} registered twice")
        _REGISTRY[name] = factory
        return factory

    return deco


register_collective("none")(lambda block: ExactCollective("none", block))
register_collective("fp16")(lambda block: Fp16Collective("fp16", block))
register_collective("int8")(lambda block: Int8Collective("int8", block))
register_collective("fp8_e4m3")(
    lambda block: Fp8E4M3Collective("fp8_e4m3", block)
)
register_collective("fp8_e5m2")(
    lambda block: Fp8E5M2Collective("fp8_e5m2", block)
)


def available_collectives() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_collective(
    name: Optional[str] = None, block: Optional[int] = None
) -> GradientCollective:
    """Resolves a collective; None args read the central flag registry
    (T2R_COLLECTIVE_QUANT / T2R_COLLECTIVE_BLOCK)."""
    if name is None:
        name = flags.get_enum("T2R_COLLECTIVE_QUANT")
    if block is None:
        block = flags.get_int("T2R_COLLECTIVE_BLOCK")
    factory = _REGISTRY.get(name)
    if factory is None:
        # Name the selector AND the menu: a typo'd regime must tell the
        # operator what values exist and which flag picks one (the same
        # name-the-flag discipline as the flags.py getters).
        raise KeyError(
            f"unknown collective {name!r}; available regimes: "
            f"{', '.join(available_collectives())} "
            "(selected by T2R_COLLECTIVE_QUANT, block size by "
            "T2R_COLLECTIVE_BLOCK)"
        )
    return factory(block)


# -- flat shard layout ---------------------------------------------------------


class FlatShardLayout:
    """Pad-to-block bookkeeping for the flat sharded weight update.

    The quantized ZeRO-2 step works on the RAVELED gradient/parameter
    vector so every device owns one contiguous [shard_len] shard whose
    length divides by the quantization block. num_params elements pad
    with zeros up to padded = num_shards * shard_len; zero-padded tail
    elements carry zero gradient forever, so standard elementwise
    optimizers (Adam & friends) keep their tail params at exactly zero.
    """

    def __init__(self, num_params: int, num_shards: int, block: int):
        if num_params < 1:
            raise ValueError("empty parameter vector")
        if num_shards < 1 or block < 1:
            raise ValueError(
                f"bad layout: shards={num_shards} block={block}"
            )
        shard_len = -(-num_params // num_shards)
        shard_len = -(-shard_len // block) * block
        self.num_params = num_params
        self.num_shards = num_shards
        self.block = block
        self.shard_len = shard_len
        self.padded = shard_len * num_shards

    def pad(self, flat: jax.Array) -> jax.Array:
        if flat.shape != (self.num_params,):
            raise ValueError(
                f"expected [{self.num_params}] vector, got {flat.shape}"
            )
        return jnp.pad(flat, (0, self.padded - self.num_params))

    def rows(self, flat_padded: jax.Array) -> jax.Array:
        return flat_padded.reshape(self.num_shards, self.shard_len)

    def unpad(self, flat_padded: jax.Array) -> jax.Array:
        return flat_padded[: self.num_params]


def wire_summary(
    collective: GradientCollective, n_elements: int
) -> Tuple[int, int]:
    """(fp32_bytes, wire_bytes) per device-step for the ZeRO-2 exchange:
    one reduce-scatter of the gradient plus one all-gather of the update,
    each moving n_elements through the collective's wire format. Callers
    format these through train.metrics.collective_record so the trainer's
    log stream and the bench payload share key names."""
    return 2 * 4 * n_elements, 2 * collective.wire_bytes(n_elements)
