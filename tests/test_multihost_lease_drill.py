"""The ADR-5 capstone drills: TWO OS processes form ONE jax.distributed
SPMD world, lose a rank to SIGKILL mid-job, and must shrink, relaunch,
grow back and converge — split from test_elasticity_drill.py so `--dist
loadfile` can balance them.

Tier-1 keeps the pure-DP and quantized-DP worlds. The DP x TP, ZeRO-1,
TP x quantized and pipeline compositions are marked `slow`: each compiles
several more programs per rank and per regroup (80+ s on an idle box,
several minutes on a loaded one), and they are the worlds whose many
independent cross-process reductions wedge gloo under load. Every case
is bounded by run_drill's one deadline whichever lane it runs in."""

import os
import sys

import numpy as np
import pytest

import test_module
from test_utils import MULTIHOST_XLA_FLAGS, coordinator_block

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from elastic_drill import run_drill  # noqa: E402

# Well inside the suite's own limit: a wedged world fails here, it does
# not eat the run.
DRILL_TIMEOUT = 300


@pytest.mark.parametrize(
    "variant,extra,env,want_axes",
    [
        # Pure elastic DP: the ADR-5 baseline.
        ("dp", (), {}, "'data': 8"),
        # DP x TP across processes: the model axis (2) lives INSIDE each
        # 4-device process, the data axis (4) spans both — the round-4
        # composition invariant. The regroup must carry TP-sharded params.
        pytest.param(
            "dp_tp",
            ("--model_parallel_size", "2"),
            {},
            "'model': 2",
            marks=pytest.mark.slow,
        ),
        # DP + ZeRO-1 across processes: {data: 2 procs, zero: 4 local}
        # mesh; adam moments shard over the intra-process zero axis and
        # must survive the SIGKILL regroup.
        pytest.param(
            "dp_zero1",
            ("--zero1",),
            {"EDL_TEST_OPT": "adam"},
            "'zero': 4",
            marks=pytest.mark.slow,
        ),
        # DP with int8-quantized gradient reduction across processes:
        # the EQuARX wire format under real elasticity — training must
        # converge through the SIGKILL regroup with quantized collectives.
        (
            "dp_quantized",
            ("--quantized_grads",),
            {},
            "'data': 8",
        ),
        # DP x TP x QUANTIZED across processes: the flagship north-star
        # composition (multi-host data axis, intra-host model axis) with
        # the cross-process gradient mean quantized — the exact DCN leg
        # EQuARX targets — surviving a SIGKILL regroup.
        # Un-xfailed: the "never starts on 1-core boxes" diagnosis was
        # wrong — workers were SIGABRTing in a fatal XLA SPMD-partitioner
        # check (all_to_all/all_gather are unpartitionable inside a
        # partial-auto shard_map through jax 0.4.x), which the master's
        # relaunch loop made look like a startup stall. The TP variant
        # now reduces through quantized_pmean's psum-lane formulation
        # (parallel/quantized.py), which that partitioner regime handles.
        pytest.param(
            "dp_tp_quantized",
            ("--model_parallel_size", "2", "--quantized_grads"),
            {},
            "'model': 2",
            marks=pytest.mark.slow,
        ),
        # DP x PIPELINE across processes: the stage axis (2) lives inside
        # each 4-device process (same composition invariant as dp_tp),
        # microbatches flow through the GPipe schedule, and the staged
        # param tree must survive the SIGKILL regroup. Adam because the
        # factored toy diverges under the default sgd lr.
        pytest.param(
            "dp_pp",
            (
                "--pipeline_stages", "2",
                "--pipeline_schedule", "gpipe",
                "--pipeline_microbatches", "2",
            ),
            {"EDL_TEST_OPT": "adam"},
            "'stage': 2",
            marks=pytest.mark.slow,
        ),
    ],
)
def test_kill_worker_mid_job_multihost_lease_drill(
    tmp_path, variant, extra, env, want_axes
):
    """The ADR-5 capstone: TWO OS processes form ONE jax.distributed SPMD
    world (4 virtual CPU devices each = 8-device global mesh), training
    through step-synchronized task leases. SIGKILLing one worker mid-job
    must shrink the world to the 4-device survivor, relaunch the worker,
    grow back to 8, and complete with a converged model — the reference's
    elastic Horovod behavior (allreduce/report.md) at full process scope.
    The TP and ZeRO-1 variants prove the north-star composition (VERDICT
    r3 #1): parallelism beyond plain DP crossing processes AND surviving
    an elastic regroup."""
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    data = str(tmp_path / "linear.edlr")
    with RecordFileWriter(data) as w:
        for r in test_module.make_linear_records(256):
            w.write(r)
    output = str(tmp_path / "model.npz")
    result = run_drill(
        data,
        model_zoo=os.path.join(REPO, "tests"),
        model_def="test_module",
        num_workers=2,
        num_ps=0,
        strategy="AllreduceStrategy",
        num_epochs=120,
        minibatch_size=32,
        records_per_task=64,
        extra_args=(
            "--multi_host",
            "--coordinator_port",
            str(coordinator_block()),
            "--output",
            output,
            *extra,
        ),
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": MULTIHOST_XLA_FLAGS,
            **env,
        },
        timeout=DRILL_TIMEOUT,
        # A SIGSTOPped rank would stall the whole SPMD world's
        # collectives; this drill asserts rejoin, not task recovery.
        require_victim_task=False,
    )
    assert result["completed"], result.get("log_tail", "")[-1500:]
    assert result["relaunched"], "worker was never relaunched"
    assert result["rejoin_s"] is not None, result
    # The requested mesh really formed (no silent DP fallback).
    assert any(
        want_axes in axes for axes in result["mesh_axes_seen"]
    ), (want_axes, result["mesh_axes_seen"])
    with np.load(output) as d:
        if variant == "dp_pp":
            # Staged tree: check the effective end-to-end weights.
            kernel, bias = test_module.pipeline_effective_weights(d)
            assert abs(bias - test_module.TRUE_B) < 0.1
        else:
            kernel = d["params/Dense_0/kernel"].reshape(-1)
    np.testing.assert_allclose(kernel, test_module.TRUE_W, atol=0.1)
