"""The gated short convolution of the LFM2 family (HF `lfm2` / `lfm2_moe`,
`Lfm2ShortConv`): a depthwise causal convolution over a few positions
between two elementwise gates, in place of attention.

    B, C, x = split(in_proj(u), 3)                # no bias, no activation
    z = B * x
    c_t = sum_{j < L} w[j] * z_{t-L+1+j}          # a channel, zeros before
                                                  # the sequence; L =
                                                  # conv_L_cache (3)
    out = out_proj(C * c)

The two projections are matrix products; everything between them is
elementwise over [B, S, 3 d] and bound by memory. It runs under
`jax.named_scope("short_conv")` so that a device trace finds it.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.mamba2 import _uniform, causal_depthwise_conv

CONV_SCOPE = "short_conv"


class GatedShortConv(nn.Module):
    d_model: int
    conv_kernel: int = 3
    use_conv_bias: bool = False
    dtype: str = "bfloat16"
    kernel_init: nn.initializers.Initializer = nn.initializers.normal(0.02)

    @nn.compact
    def __call__(self, u):
        dtype = jnp.dtype(self.dtype)
        d, taps = self.d_model, self.conv_kernel

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=dtype,
                            kernel_init=self.kernel_init, name=name)

        with jax.named_scope(CONV_SCOPE):
            b, c, x = jnp.split(dense(3 * d, "in_proj")(u), 3, axis=-1)
            weight = self.param(
                "conv_kernel", _uniform(taps ** -0.5), (taps, d))
            bias = self.param(
                "conv_bias", _uniform(taps ** -0.5), (d,)
            ) if self.use_conv_bias else None
            conv = causal_depthwise_conv(
                b * x, weight.astype(dtype),
                None if bias is None else bias.astype(dtype))
            return dense(d, "out_proj")(c * conv)
