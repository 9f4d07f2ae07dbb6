"""Transformer LM flagship: trains on synthetic Markov text toward the
log(branching) CE floor; the DP+SP (ring attention) sharded step from
__graft_entry__ runs on the virtual 8-device mesh."""

import dataclasses

import numpy as np
import pytest

from elasticdl_tpu.data.gen.synthetic import synthetic_lm_tokens
from elasticdl_tpu.models.transformer import transformer_lm as tlm
from elasticdl_tpu.worker.trainer import LocalTrainer


def test_lm_loss_drops_toward_markov_floor():
    cfg = tlm.LMConfig(
        vocab=32, d_model=64, n_heads=2, n_layers=1, max_len=64
    )
    trainer = LocalTrainer(
        tlm.custom_model(cfg), tlm.loss, tlm.optimizer(), seed=0
    )
    seqs = synthetic_lm_tokens(
        512, seq_len=64, vocab=32, branching=2, seed=1
    )
    first = last = None
    for step in range(60):
        batch = seqs[(step * 16) % 496 : (step * 16) % 496 + 16]
        features, labels = batch[:, :-1], batch[:, 1:]
        _, _, loss = trainer.train_minibatch(features, labels)
        if first is None:
            first = loss
        last = loss
    # Random guessing = log(32) ~ 3.47; floor = log(2) ~ 0.69.
    assert first > 3.0
    assert last < 2.0, (first, last)


def _four_losses(cfg):
    trainer = LocalTrainer(
        tlm.custom_model(cfg), tlm.loss, tlm.optimizer(), seed=0
    )
    seqs = synthetic_lm_tokens(64, seq_len=64, vocab=32, branching=2, seed=1)
    losses = []
    for i in range(4):
        rows = seqs[16 * i : 16 * i + 16]
        losses.append(
            float(trainer.train_minibatch(rows[:, :-1], rows[:, 1:])[2])
        )
    return losses


def test_activation_dtype_crosses_the_attention_boundary(monkeypatch):
    """The local path hands flash_attention q, k, v in the activation
    dtype (the op runs its softmax in float32 itself) where it used to
    upcast them at the call site and round the result back; a callable in
    `attention=` (the context-parallel path) is still handed float32. Four
    steps of the tiny LM either way: the same values reach every product,
    and `delta` reads the rounded o."""
    from elasticdl_tpu.ops.flash_attention import flash_attention

    cfg = tlm.LMConfig(
        vocab=32, d_model=64, n_heads=2, n_layers=2, max_len=64
    )
    assert cfg.activation_dtype == "bfloat16" and cfg.attention is None
    local, handed = [], []
    real = tlm._default_attention

    def recording(q, k, v):
        out = real(q, k, v)
        local.extend(x.dtype for x in (q, k, v, out))
        return out

    def casts_at_the_call_site(q, k, v):
        handed.extend(x.dtype for x in (q, k, v))
        return flash_attention(q, k, v, True)

    monkeypatch.setattr(tlm, "_default_attention", recording)
    losses = _four_losses(cfg)
    with_casts = _four_losses(
        dataclasses.replace(cfg, attention=casts_at_the_call_site)
    )
    assert local and set(local) == {np.dtype("bfloat16")}
    assert handed and set(handed) == {np.dtype("float32")}
    assert losses[0] > 3.0
    np.testing.assert_allclose(losses, with_casts, atol=1e-3, rtol=0)


@pytest.mark.slow  # fifteen sharded phases, each its own compiles: ~95 s
def test_dryrun_multichip_dp_sp():
    import __graft_entry__ as graft

    graft.dryrun_multichip(8)


def test_remat_policy_validation():
    import pytest

    from elasticdl_tpu.models.transformer.transformer_lm import LMConfig

    with pytest.raises(ValueError, match="remat=False"):
        LMConfig(remat_policy="dots_with_no_batch_dims_saveable")
    with pytest.raises(ValueError, match="unknown remat_policy"):
        LMConfig(remat=True, remat_policy="not_a_policy")
    LMConfig(remat=True, remat_policy="dots_with_no_batch_dims_saveable")
