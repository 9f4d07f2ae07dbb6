"""Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs"), as the HF
`nemotron_h` model sizes it.

    z, xBC, dt = in_proj(u)                       # no bias
    xBC = silu(causal depthwise conv1d(xBC) + bias)
    x [H, P], B [G, N], C [G, N] = split(xBC)     # H / G heads share a group
    dt = softplus(dt + dt_bias), within time_step_limit
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,   A = -exp(A_log), a scalar a head
    y_t = C_t h_t + D x_t
    out = out_proj(groupRMSNorm(y * silu(z)) * weight)

The recurrence is computed in the chunked (SSD) form, `ssd_chunked`: inside
a chunk of Q tokens it is a masked [Q, Q] product, between chunks a short
recurrence over one state a chunk, so nearly all of it is matrix products.
Decays (cumulative sums and their exponentials) stay float32; the products
take operands in the activation dtype and accumulate in float32. The
gradient is `jax.grad` of the same products. Plain `jax.numpy`: the four
phases run under `jax.named_scope("ssd_scan")` so that a device trace finds
them. `Mamba2Mixer.scan` is the function the mixer scans with: `ssd_chunked`
unless the call site hands in another with the same arguments and result
(`ops/ssd_scan.py:ssd_scan`, the same mathematics as Pallas kernels);
`Mamba2Mixer.conv` the convolution stage in the same way: `conv_silu_split`
unless the call site hands in `ops/causal_conv.py:causal_conv_silu`.
"""

import math
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

SCAN_SCOPE = "ssd_scan"


def ssd_chunked(x, dt, a, b, c, chunk, dtype=jnp.float32):
    """y_t = C_t h_t with h_t = exp(dt_t a) h_{t-1} + dt_t B_t x_t^T.

    x [B, S, H, P]; dt [B, S, H] float32 (after softplus); a [H] float32
    (negative); b, c [B, S, G, N] with H a multiple of G (head h reads
    group h // (H / G)). Returns y [B, S, H, P] float32 (without the D
    skip). S must be a multiple of `chunk`.
    """
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if s % chunk:
        raise ValueError(
            f"ssd_chunked: sequence length {s} is not a multiple of the "
            f"chunk {chunk}; pad the sequence or pick a chunk that divides it"
        )
    if h % g:
        raise ValueError(f"ssd_chunked: {h} heads do not split over {g} groups")
    r, nc, f32 = h // g, s // chunk, jnp.float32

    def dot(spec, *operands):
        return jnp.einsum(
            spec, *(o.astype(dtype) for o in operands),
            preferred_element_type=f32,
        )

    with jax.named_scope(SCAN_SCOPE):
        # Log-decay a step, and its running sum inside each chunk.
        da = (dt.astype(f32) * a.astype(f32)).reshape(bsz, nc, chunk, g, r)
        cum = jnp.cumsum(da, axis=2)                      # [B, C, Q, G, R]
        total = cum[:, :, -1]                             # [B, C, G, R]
        xdt = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(
            bsz, nc, chunk, g, r, p)
        bc = b.reshape(bsz, nc, chunk, g, n)
        cc = c.reshape(bsz, nc, chunk, g, n)

        # 1. Inside a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s)
        #    dt_s x_s. C B^T is one product a group, the decay one mask a head.
        cb = dot("bclgn,bcsgn->bcgls", cc, bc)            # [B, C, G, Q, Q]
        cum_h = jnp.moveaxis(cum, 2, -1)                  # [B, C, G, R, Q]
        seg = cum_h[..., :, None] - cum_h[..., None, :]   # l, s
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        y = dot("bcgrls,bcsgrp->bclgrp", cb[:, :, :, None] * decay, xdt)

        # 2. What each chunk adds to the state by its end.
        to_end = jnp.exp(total[:, :, None] - cum)         # [B, C, Q, G, R]
        added = dot("bcsgn,bcsgrp->bcgrpn", bc, xdt * to_end[..., None])

        # 3. The state entering each chunk: a recurrence over chunks,
        #    written as one [C, C] masked product.
        run = jnp.cumsum(total, axis=1)                   # [B, C, G, R]
        run = jnp.moveaxis(run, 1, -1)                    # [B, G, R, C]
        # state entering chunk z holds chunk y < z decayed over chunks
        # y+1 .. z-1: exp(run[z-1] - run[y]).
        before = jnp.pad(run, ((0, 0),) * 3 + ((1, 0),))[..., :-1]
        span = before[..., :, None] - run[..., None, :]   # z, y
        earlier = jnp.tril(jnp.ones((nc, nc), bool), k=-1)
        carry = jnp.exp(jnp.where(earlier, span, -jnp.inf))
        entering = jnp.einsum(
            "bgrzy,bygrpn->bzgrpn", carry, added,
            precision=jax.lax.Precision.HIGHEST)          # float32 states

        # 4. What the entering state gives each token of the chunk.
        y = y + dot("bclgn,bcgrpn->bclgrp", cc, entering) \
            * jnp.exp(cum)[..., None]
    return y.reshape(bsz, s, h, p)


def causal_depthwise_conv(x, weight, bias):
    """x [B, S, C], weight [K, C], bias [C] or None: y_t = sum_j w_j
    x_{t-K+1+j} + bias, zeros before the sequence."""
    k = weight.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s] * weight[j] for j in range(k))
    return y if bias is None else y + bias


def conv_silu_split(proj, widths, weight, bias):
    """The mixer's convolution stage. proj [B, S, sum(widths)] holds z, x,
    B, C and dt side by side in `widths`; weight [K, C] and bias [C] or
    None (the parameters, taken in proj's dtype) over C = x's, B's and
    C's widths. Returns z, x, B, C, dt with x, B, C =
    split(silu(causal_depthwise_conv(x | B | C, weight, bias)))."""
    z, xbc, dt = jnp.split(
        proj, [widths[0], sum(widths[:4])], axis=-1)
    xbc = jax.nn.silu(causal_depthwise_conv(
        xbc, weight.astype(proj.dtype),
        None if bias is None else bias.astype(proj.dtype)))
    x, b, c = jnp.split(xbc, [widths[1], widths[1] + widths[2]], axis=-1)
    return z, x, b, c, dt


def gated_group_rms_norm(y, z, weight, groups, eps):
    """weight * groupRMSNorm(y * silu(z)) over `groups` groups of the last
    axis, in float32."""
    f32 = jnp.float32
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    shape = v.shape
    v = v.reshape(*shape[:-1], groups, shape[-1] // groups)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v.reshape(shape) * weight.astype(f32)


def _dt_bias_init(dt_min, dt_max, dt_floor):
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        # The inverse of softplus: softplus(dt_bias) = dt.
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(
        key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _uniform(scale):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -scale, scale)
    return init


class Mamba2Mixer(nn.Module):
    d_model: int
    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    time_step_limit: Tuple[float, Optional[float]] = (0.0, None)
    dtype: str = "bfloat16"
    kernel_init: nn.initializers.Initializer = nn.initializers.normal(0.02)
    # (x, dt, a, b, c, chunk, dtype=) -> y, as `ssd_chunked`.
    scan: Callable = ssd_chunked
    # (proj, widths, weight, bias) -> z, x, b, c, dt, as `conv_silu_split`.
    conv: Callable = conv_silu_split

    @nn.compact
    def __call__(self, u):
        dtype = jnp.dtype(self.dtype)
        f32 = jnp.float32
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        d_inner, bsz, s = h * p, u.shape[0], u.shape[1]
        d_conv = d_inner + 2 * g * n
        proj = nn.Dense(
            d_inner + d_conv + h, use_bias=False, dtype=dtype,
            kernel_init=self.kernel_init, name="in_proj")(u)
        conv_w = self.param(
            "conv_kernel", _uniform(self.conv_kernel ** -0.5),
            (self.conv_kernel, d_conv))
        conv_b = self.param(
            "conv_bias", _uniform(self.conv_kernel ** -0.5), (d_conv,)
        ) if self.use_conv_bias else None
        z, x, b, c, dt = self.conv(
            proj, (d_inner, d_inner, g * n, g * n, h), conv_w, conv_b)
        x = x.reshape(bsz, s, h, p)
        b = b.reshape(bsz, s, g, n)
        c = c.reshape(bsz, s, g, n)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(
                self.time_step_min, self.time_step_max,
                self.time_step_floor), (h,))
        a_log = self.param("A_log", _a_log_init, (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        low, high = self.time_step_limit
        if low or high is not None:
            dt = jnp.clip(dt, low, high)
        y = self.scan(x, dt, -jnp.exp(a_log.astype(f32)), b, c,
                      self.chunk_size, dtype=dtype)
        y = y + x.astype(f32) * skip.astype(f32)[:, None]
        weight = self.param("norm_weight", nn.initializers.ones, (d_inner,))
        y = gated_group_rms_norm(
            y.reshape(bsz, s, d_inner), z, weight, g, self.norm_eps)
        return nn.Dense(
            self.d_model, use_bias=False, dtype=dtype,
            kernel_init=self.kernel_init, name="out_proj")(y.astype(dtype))
