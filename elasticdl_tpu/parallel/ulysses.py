"""Ulysses-style all-to-all sequence parallelism.

Capability extension beyond the DP-only reference. Activations arrive
sequence-sharded [B, H, S_local, D]; an all-to-all over the sequence axis
re-shards them to head-sharded [B, H_local, S_global, D], where each device
runs FULL attention over the whole sequence for its head subset (flash
attention locally), and a second all-to-all restores sequence sharding.
Two all-to-alls per attention vs ring's N-1 ppermutes: better for moderate
sequence lengths when heads >= devices; ring wins when S_global's K/V
can't fit per device.
"""

import functools

import jax

from elasticdl_tpu.ops.flash_attention import flash_attention
from elasticdl_tpu.parallel.ring_attention import (
    refuse_mask_description,
    refuse_unequal_widths,
)


def ulysses_attention(q, k, v, axis_name, attention_fn=None, causal=False):
    """Call INSIDE shard_map with q/k/v local blocks [B, H, S_local, D].
    Requires num_heads % axis_size == 0."""
    refuse_mask_description(causal)
    refuse_unequal_widths(q, v)
    if attention_fn is None:
        # Flash attention by default: the whole point of the re-shard is
        # attending over S_global, and a full score matrix there is the
        # quadratic memory this path exists to avoid.
        attention_fn = functools.partial(flash_attention, mask=causal)
    axis_size = jax.lax.psum(1, axis_name)
    h = q.shape[1]
    if h % axis_size:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by the seq axis "
            f"({axis_size})"
        )

    def seq_to_heads(x):
        # [B, H, S_local, D] -> [B, H/N, S_global, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    out = attention_fn(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v))
    return heads_to_seq(out)


def make_ulysses_attention(
    mesh, axis_name="seq", attention_fn=None, causal=False,
    batch_axis=None,
):
    """shard_map-wrapped Ulysses attention over GLOBAL [B, H, S, D] arrays
    sharded on S (and optionally on B along `batch_axis`)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    spec = P(batch_axis, None, axis_name, None)
    return shard_map(
        functools.partial(
            ulysses_attention,
            axis_name=axis_name,
            attention_fn=attention_fn,
            causal=causal,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
