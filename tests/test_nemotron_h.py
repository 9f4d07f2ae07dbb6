"""The Nemotron-H model package: the config from public keys, the cut, the
model contract, and the step statistics through the trainers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.nemotron_h import nemotron_h as nh
from elasticdl_tpu.models.nemotron_h import nemotron_h_twotower_cut as cut
from elasticdl_tpu.worker import trainer as trainer_mod

TINY = nh.NemotronHConfig(
    hybrid_override_pattern="ME*EM", experts_held=(2, 4),
    expert_block_rows=8)


def tokens(batch=2, seq=16, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=(batch, seq)).astype(np.int32)


def test_from_public_takes_the_public_keys_and_cuts_the_pattern():
    cfg = nh.NemotronHConfig.from_public(
        dict(cut.PUBLIC_CONFIG, num_hidden_layers=9), experts_held=[0, 8])
    assert cfg.hybrid_override_pattern == "MEMEM*EME"
    assert cfg.hidden_size == 2688 and cfg.n_routed_experts == 128
    assert cfg.num_experts_per_tok == 6 and cfg.routed_scaling_factor == 2.5
    assert cfg.mamba_num_heads * cfg.mamba_head_dim == 4096
    assert cfg.time_step_limit == (0, None) and cfg.experts_held == (0, 8)
    assert not hasattr(cfg, "rope_theta")  # unused by the HF attention


def test_the_full_pattern_is_52_layers_in_the_published_ratio():
    pattern = cut.PUBLIC_CONFIG["hybrid_override_pattern"]
    assert len(pattern) == 52
    assert [pattern.count(c) for c in "ME*"] == [23, 23, 6]
    assert pattern[:9] == "MEMEM*EME"


def test_the_cut_has_the_published_widths_and_the_reckoned_parameters():
    model = cut.custom_model()
    cfg = model.config
    assert (cfg.vocab_size, cfg.experts_held, cfg.chunk_size) == (
        16384, (0, 8), 128)
    shapes = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 256), jnp.int32),
                             training=False), jax.random.PRNGKey(0))
    p = shapes["params"]
    count = lambda t: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(t))
    assert count(p["layers_0"]) == 38_744_896       # M
    assert count(p["layers_5"]) == 23_399_040       # *
    assert count(p["layers_1"]) == 100_125_312      # E: 8 experts held
    assert count(p) == 666_962_944
    mixer = p["layers_0"]["mixer"]
    assert mixer["in_proj"]["kernel"].shape == (2688, 4096 + 6144 + 64)
    assert p["layers_1"]["mixer"]["router"].shape == (128, 2688)
    assert p["layers_1"]["mixer"]["w_up"].shape == (8, 2688, 1856)
    assert p["layers_1"]["mixer"]["shared_up"]["kernel"].shape == (
        2688, 3712)
    assert p["layers_5"]["mixer"]["k_proj"]["kernel"].shape == (
        2688, 2, 128)
    assert p["lm_head"]["kernel"].shape == (2688, 16384)
    assert all(a.dtype == jnp.float32 for a in jax.tree_util.tree_leaves(p))


@pytest.mark.parametrize("bad", ["", "MXE", "mE", "ME-"])
def test_unknown_pattern_letters_are_refused(bad):
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nh.NemotronHConfig(hybrid_override_pattern=bad)


def test_query_heads_must_split_over_key_value_heads():
    with pytest.raises(ValueError, match="key/value"):
        nh.NemotronHConfig(num_attention_heads=6, num_key_value_heads=4)


def test_training_output_carries_stats_and_evaluation_plain_logits():
    model = nh.custom_model(TINY)
    x = jnp.asarray(tokens())
    variables = model.init({"params": jax.random.PRNGKey(0)}, x,
                           training=False)
    assert set(variables) == {"params", "buffers"}
    logits = model.apply(variables, x, training=False)
    assert logits.shape == (2, 16, 256) and logits.dtype == jnp.float32
    out = model.apply(variables, x, training=True)
    assert set(out) == {"logits", "stats"}
    assert float(out["stats"]["moe_assignments"]) == 2 * 2 * 16 * 2
    np.testing.assert_array_equal(out["logits"], logits)


def test_remat_changes_no_value():
    x = jnp.asarray(tokens())
    plain = nh.custom_model(TINY)
    variables = plain.init({"params": jax.random.PRNGKey(0)}, x,
                           training=False)

    def grads(model):
        def loss(p):
            out = model.apply({**variables, "params": p}, x, training=True)
            return nh.loss(x, out)
        return jax.value_and_grad(loss)(variables["params"])

    want_loss, want = grads(plain)
    got_loss, got = grads(nh.custom_model(
        dataclasses.replace(TINY, remat=True)))
    assert float(want_loss) == pytest.approx(float(got_loss), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def _tiny(which):
    """(model, loss, optimizer) of the tiny dense LM or the tiny hybrid."""
    if which == "hybrid":
        return nh.custom_model(TINY), nh.loss, nh.optimizer()
    from elasticdl_tpu.models.transformer import transformer_lm as tlm

    return tlm.custom_model(), tlm.loss, tlm.optimizer()


def _barriers_outside_the_routing_stage(step, *args):
    """The optimisation barriers a step is traced with, but the routed
    layers' own: `layers/moe.py` hands the router's pick over behind one,
    forward and backward, in its named scope."""
    from elasticdl_tpu.layers.moe import ROUTING_SCOPE

    def count(jaxpr):
        # An inner jaxpr's name stacks start at its equation's.
        return sum(
            (eqn.primitive.name == "optimization_barrier")
            + sum(map(count, jax.core.jaxprs_in_params(eqn.params)))
            for eqn in jaxpr.eqns
            if ROUTING_SCOPE not in str(eqn.source_info.name_stack))

    return count(jax.make_jaxpr(step)(*args).jaxpr)


@pytest.mark.parametrize("which", ["lm", "hybrid"])
def test_the_update_apart_changes_no_value(which):
    """A one-device step keeps the optimizer's update out of the
    weight-gradient products' fusions with a barrier a gradient leaf
    (`_step_body`'s `update_apart`): the same values reach Adam, so four
    steps with and without it give the same losses and parameters."""
    import functools

    x = tokens(2, 17, 1)

    def four_steps(update_apart):
        model, loss_fn, optimizer = _tiny(which)
        trainer = trainer_mod.LocalTrainer(model, loss_fn, optimizer, seed=3)
        trainer.init_variables_if_needed(x[:, :-1])
        if update_apart:
            # What LocalTrainer always builds: nothing reduces its
            # gradients over devices.
            assert _barriers_outside_the_routing_stage(
                trainer._train_step, trainer._variables, trainer._opt_state,
                jax.random.PRNGKey(0), jnp.asarray(x[:, :-1]),
                jnp.asarray(x[:, 1:])) == len(
                jax.tree_util.tree_leaves(trainer._variables["params"]))
        else:
            trainer._train_step = jax.jit(
                functools.partial(trainer._step_body, update_apart=False),
                donate_argnums=(0, 1))
        losses = []
        for step in range(4):
            batch = tokens(2, 17, step)
            _, _, loss = trainer.train_minibatch(batch[:, :-1], batch[:, 1:])
            losses.append(float(loss))
        return losses, trainer.export_variables()["variables"]["params"]

    apart_losses, apart = four_steps(True)
    fused_losses, fused = four_steps(False)
    assert apart_losses[-1] != apart_losses[0]
    np.testing.assert_allclose(apart_losses, fused_losses, rtol=0, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(apart),
                    jax.tree_util.tree_leaves(fused)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


@pytest.mark.parametrize("n_devices, apart", [(1, True), (4, False)])
def test_the_step_event_says_whether_the_update_is_apart(
        tmp_path, n_devices, apart):
    """`update_apart` rides on the step's compile event beside
    `dp_overlap`: true where the gradients are not reduced over devices
    (`LocalTrainer`'s `train_step` always, the sharded step on a mesh of
    one device), false on a data mesh of four, which is lowered without a
    barrier as before."""
    from jax.sharding import Mesh

    from elasticdl_tpu.observability import events as obs_events
    from elasticdl_tpu.parallel import step_plan
    from elasticdl_tpu.parallel.mesh import DATA_AXIS

    model, loss_fn, optimizer = _tiny("hybrid")
    trainer = trainer_mod.LocalTrainer(model, loss_fn, optimizer, seed=3)
    x = tokens(4, 17, 1)
    log = obs_events.EventLog(
        str(tmp_path / "events.jsonl"), job="t", role="test")
    prev = obs_events.get_event_log()
    obs_events.set_event_log(log)
    try:
        trainer.train_minibatch(x[:, :-1], x[:, 1:])
        mesh = Mesh(np.array(jax.devices()[:n_devices]), (DATA_AXIS,))
        assert step_plan.update_apart_for(mesh) is apart
        _, step = step_plan.build_step(
            step_plan.StepModel(
                step_body=trainer._step_body,
                apply_train=trainer._apply_train,
                loss_fn=loss_fn, optax=trainer._optax),
            mesh, 1, 4, trainer._variables, trainer._opt_state)
        args = (trainer._variables, trainer._opt_state,
                jax.random.PRNGKey(0), x[:, :-1], x[:, 1:])
        n_leaves = len(
            jax.tree_util.tree_leaves(trainer._variables["params"]))
        assert _barriers_outside_the_routing_stage(step, *args) == (
            n_leaves if apart else 0)
        loss = step(*args)[2]["loss"]
        assert np.isfinite(float(loss))
    finally:
        obs_events.set_event_log(prev)
        log.close()
    said = {
        e["fn"]: e for e in obs_events.read_events(
            str(tmp_path / "events.jsonl"))
        if e["kind"] in ("compile", "compile_cache_hit")
    }
    assert said["train_step"]["update_apart"] is True
    assert said["allreduce_step"]["update_apart"] is apart
    assert said["allreduce_step"]["dp_overlap"] is False


def test_a_model_without_an_attention_layer_is_causal():
    """The Mamba layers carry order: a later token changes no earlier
    logit, with or without the attention layer."""
    for pattern in ("ME", "M*E"):
        model = nh.custom_model(dataclasses.replace(
            TINY, hybrid_override_pattern=pattern,
            activation_dtype="float32"))
        x = tokens(1, 16)
        variables = model.init({"params": jax.random.PRNGKey(1)},
                               jnp.asarray(x), training=False)
        later = x.copy()
        later[:, 10:] = (later[:, 10:] + 7) % 256
        a = model.apply(variables, jnp.asarray(x), training=False)
        b = model.apply(variables, jnp.asarray(later), training=False)
        np.testing.assert_allclose(a[:, :10], b[:, :10], rtol=1e-4,
                                   atol=1e-5)
        assert float(jnp.max(jnp.abs(a[:, 10:] - b[:, 10:]))) > 1e-4


def test_the_step_hands_statistics_back_and_the_buffer_stays_zero():
    trainer = trainer_mod.LocalTrainer(
        nh.custom_model(TINY), nh.loss, nh.optimizer(), seed=3)
    x = tokens(2, 17, 1)
    for _ in range(3):
        _, _, loss = trainer.train_minibatch(x[:, :-1], x[:, 1:])
    assert np.ndim(loss) == 0 and np.isfinite(float(loss))
    stats = trainer.last_step_stats
    assert set(stats) == {"moe_assignments", "moe_assignments_held",
                          "moe_held_load_max", "moe_held_load_mean"}
    assert float(stats["moe_assignments"]) == 2 * 2 * 16 * 2
    assert float(stats["moe_held_load_max"]) >= float(
        stats["moe_held_load_mean"])
    for bias in jax.tree_util.tree_leaves(trainer._variables["buffers"]):
        assert not np.asarray(bias).any()


def test_a_model_without_statistics_keeps_its_step_program():
    """The dense LM's step returns the bare loss: (variables, opt_state,
    loss) as before, no further output."""
    from elasticdl_tpu.models.transformer import transformer_lm as tlm

    trainer = trainer_mod.LocalTrainer(
        tlm.custom_model(), tlm.loss, tlm.optimizer(), seed=3)
    x = tokens(2, 17, 1)
    _, _, loss = trainer.train_minibatch(x[:, :-1], x[:, 1:])
    assert trainer.last_step_stats is None and np.ndim(loss) == 0
    args = (trainer._variables, trainer._opt_state, jax.random.PRNGKey(0),
            jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:]))
    out = jax.eval_shape(trainer._step_body, *args)
    assert len(out) == 3 and out[2].shape == ()
    assert trainer_mod.split_stats(loss) == (loss, None)
    assert trainer_mod.with_stats(loss, None) is loss


def test_param_specs_replicate_every_leaf():
    from jax.sharding import PartitionSpec as P

    model = nh.custom_model(TINY)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.asarray(tokens()), training=False)
    specs = nh.param_specs(variables)
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    assert leaves and all(s == P() for s in leaves)
