"""Non-overlapping max pooling over NHWC (window == stride).

Every pool of the Grasping44 tower is of this form (reference
research/qtopt/networks.py:446,460,540). Forward and backward are
`lax.reduce_window` and its registered gradient, SelectAndScatter, on
every platform: tied maxima (common after relu: exact zeros) send the
whole incoming gradient to the first of them.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def max_pool(
    x: jax.Array, window: Tuple[int, int], padding: str = "SAME"
) -> jax.Array:
    dims = (1, window[0], window[1], 1)
    # Init must be the -inf LITERAL: jax's reverse-mode rule for max
    # pooling pattern-matches (literal init, lax.max) — a device-array
    # init turns this into a general reduce_window with no transpose.
    return lax.reduce_window(
        x, -jnp.inf, lax.max, dims, dims, padding.upper()
    )
