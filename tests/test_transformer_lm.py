"""Transformer LM flagship: trains on synthetic Markov text toward the
log(branching) CE floor; the DP+SP (ring attention) sharded step from
__graft_entry__ runs on the virtual 8-device mesh."""

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
