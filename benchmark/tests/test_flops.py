"""flops.py against a hand count of one convolution and one dense layer."""

import jax
import jax.numpy as jnp

import flops


def _net(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["k"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    return (y.reshape(y.shape[0], -1) @ p["w"]).sum()


def test_conv_and_dense_by_hand():
    p = {
        "k": jax.ShapeDtypeStruct((3, 3, 4, 8), jnp.float32),
        "w": jax.ShapeDtypeStruct((10 * 10 * 8, 5), jnp.float32),
    }
    x = jax.ShapeDtypeStruct((2, 10, 10, 4), jnp.float32)
    counted = flops.count(_net, p, x, bytes_per_element=2)
    conv_macs = 2 * 10 * 10 * 8 * (3 * 3 * 4)
    dense_macs = 2 * 800 * 5
    assert counted["equations"] == 2
    assert counted["forward_flops"] == 2 * (conv_macs + dense_macs)
    # The convolution's input is the image: forward and weight gradient.
    # The dense layer's input depends on the parameters: three passes.
    assert counted["step_flops"] == 2 * (2 * conv_macs + 3 * dense_macs)
    conv_elements = 2 * 10 * 10 * 4 + 3 * 3 * 4 * 8 + 2 * 10 * 10 * 8
    dense_elements = 2 * 800 + 800 * 5 + 2 * 5
    assert counted["step_bytes"] == 2 * (2 * conv_elements + 3 * dense_elements)


def test_strided_convolution_counts_outputs():
    macs, elements = flops.conv_forward(
        (1, 8, 8, 3), (2, 2, 3, 16), (1, 4, 4, 16),
        jax.lax.conv_dimension_numbers(
            (1, 8, 8, 3), (2, 2, 3, 16), ("NHWC", "HWIO", "NHWC")
        ),
    )
    assert macs == 4 * 4 * 16 * 3 * 2 * 2
    assert elements == 8 * 8 * 3 + 2 * 2 * 3 * 16 + 4 * 4 * 16
