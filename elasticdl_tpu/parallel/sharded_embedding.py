"""Device-sharded embedding tables: rows across the mesh, lookup by
collectives — the TPU-first middle tier between "replicate the table" and
"host it on a parameter server".

The reference's only answer to a big table is the PS (EmbeddingDelegate
RPCs mid-forward, /root/reference/elasticdl/python/elasticdl/
embedding_delegate.py:74-106). On TPU, a table that exceeds one chip's HBM
but fits the SLICE's aggregate HBM should live sharded across the mesh and
be looked up with on-chip collectives riding ICI — the SparseCore-style
placement — keeping the PS for tables that don't fit the slice
(common/model_handler.py's threshold logic gains this as its upper tier).

Lookup pattern (inside shard_map, per device):
    1. all_gather the ids over the axis — every device sees the global
       id batch (ids are int32; this is the cheap collective),
    2. gather locally: each device answers the ids that fall in its row
       block, contributing zeros elsewhere,
    3. psum_scatter the stacked answers back — each requester receives
       exactly its batch shard's rows, summed over owners (one owner per
       id, the rest contributed zeros).
Autodiff reverses it for free: psum_scatter transposes to all_gather and
the masked gather transposes to a scatter-add into the local row block, so
the backward pass routes each row-gradient to the owning device with the
same two collectives.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

import flax.linen as nn


def padded_vocab(vocab, n_shards):
    """Rows are block-sharded; the table allocates vocab rounded up so
    every device owns an equal block (the pad rows are never addressed)."""
    return -(-vocab // n_shards) * n_shards


def sharded_embedding_lookup(table, ids, mesh, axis="data"):
    """Global [V, D] table (V divisible by the axis size) x [..., F] ids
    (leading dim sharded over `axis`) -> [..., F, D] rows with the ids'
    sharding. Call inside jit; the shard_map makes the collective pattern
    explicit instead of trusting the SPMD partitioner's gather handling."""
    n = mesh.shape[axis]

    def local(table_loc, ids_loc):
        # table_loc [V/n, D]; ids_loc [b, ...]: this device's batch shard.
        rows_per = table_loc.shape[0]
        rank = jax.lax.axis_index(axis)
        all_ids = jax.lax.all_gather(ids_loc, axis)  # [n, b, ...]
        rel = all_ids.astype(jnp.int32) - rank * rows_per
        mine = jnp.logical_and(rel >= 0, rel < rows_per)
        rows = jnp.take(
            table_loc, jnp.clip(rel, 0, rows_per - 1), axis=0
        )  # [n, b, ..., D]
        rows = jnp.where(mine[..., None], rows, 0.0)
        # [n, b, ..., D] -> [b, ..., D]: requester d gets sum over owners
        # of their answer block d (exactly one nonzero owner per id).
        # tiled psum_scatter keeps a leading block dim of n/n = 1.
        return jax.lax.psum_scatter(
            rows, axis, scatter_dimension=0, tiled=True
        )[0]

    in_rank = ids.ndim
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, *([None] * (in_rank - 1)))),
        out_specs=P(axis, *([None] * in_rank)),
        check_vma=False,
    )(table, ids)


class ShardedEmbed(nn.Module):
    """Drop-in nn.Embed analog whose table rows shard over a mesh axis.

    The param keeps the name ("embedding") and logical [vocab_padded, D]
    shape of a stock embed, so checkpoints transfer; pass
    `sharded_embed_specs` output through the trainer/jit in_shardings so
    the param is physically placed row-sharded."""

    num_embeddings: int
    features: int
    mesh: object  # jax.sharding.Mesh (static for the module tree)
    axis: str = "data"
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, ids):
        n = self.mesh.shape[self.axis]
        vocab = padded_vocab(self.num_embeddings, n)
        table = self.param(
            "embedding",
            nn.initializers.normal(stddev=0.01),
            (vocab, self.features),
            self.param_dtype,
        )
        return sharded_embedding_lookup(
            table, jnp.asarray(ids), self.mesh, self.axis
        )


def sharded_embed_spec(axis="data"):
    """PartitionSpec for a ShardedEmbed (or any row-sharded) table."""
    return P(axis, None)


def shard_table_rows(table, mesh, axis="data"):
    """Place a host/global [V, D] table row-sharded on the mesh (pads V up
    to the axis size first). Returns the global device array."""
    from jax.sharding import NamedSharding

    n = mesh.shape[axis]
    v = table.shape[0]
    vp = padded_vocab(v, n)
    if vp != v:
        table = np.concatenate(
            [np.asarray(table),
             np.zeros((vp - v, table.shape[1]), table.dtype)]
        )
    return jax.device_put(table, NamedSharding(mesh, P(axis, None)))
