"""CLI for the simulated fleet: `python -m elasticdl_tpu.fleet`.

Runs one harness for a fixed wall-clock window and prints the stats
dict as JSON — the quickest way to eyeball push-vs-pull master cost at
a given scale without writing a test around the harness:

    python -m elasticdl_tpu.fleet --pods 200 --seconds 10 --mode push
    python -m elasticdl_tpu.fleet --pods 200 --seconds 10 --mode pull
"""

import argparse
import json
import sys

from elasticdl_tpu.fleet.harness import (
    FleetHarness,
    churn_schedule,
    preemption_wave_schedule,
)


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m elasticdl_tpu.fleet",
        description="Run a simulated fleet against a real master.",
    )
    parser.add_argument("--pods", type=int, default=50,
                        help="total simulated pods (workers + PS)")
    parser.add_argument("--ps", type=int, default=0,
                        help="how many of --pods are parameter servers")
    parser.add_argument("--seconds", type=float, default=10.0,
                        help="wall-clock run time")
    parser.add_argument("--mode", choices=("push", "pull"),
                        default="push")
    parser.add_argument("--tick-interval", type=float, default=0.25,
                        help="pod scheduler tick interval (s)")
    parser.add_argument("--push-interval", type=float, default=0.5,
                        help="per-pod telemetry push interval (s)")
    parser.add_argument("--kills", type=int, default=0,
                        help="pods killed (and relaunched) by chaos")
    parser.add_argument("--stragglers", type=int, default=0,
                        help="pods slowed 4x for a chaos window")
    parser.add_argument("--preemption-wave", type=float, default=0.0,
                        help="kill this fraction of pods in ONE tick "
                             "(overrides --kills/--stragglers)")
    parser.add_argument("--lease-batch", type=int, default=1,
                        help="tasks leased/reported per RPC (batched "
                             "protocol when > 1)")
    parser.add_argument("--policy", action="store_true",
                        help="run the real policy engine against the "
                             "simulated fleet")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    n_ps = min(args.ps, args.pods)
    schedule = None
    if args.preemption_wave > 0:
        schedule = preemption_wave_schedule(
            args.pods, fraction=args.preemption_wave, seed=args.seed
        )
    elif args.kills or args.stragglers:
        schedule = churn_schedule(
            args.pods, kills=args.kills, stragglers=args.stragglers,
            seed=args.seed,
        )
    harness = FleetHarness(
        n_workers=args.pods - n_ps,
        n_ps=n_ps,
        mode=args.mode,
        tick_interval=args.tick_interval,
        push_interval=args.push_interval,
        schedule=schedule,
        seed=args.seed,
        lease_batch=args.lease_batch,
        policy=args.policy,
    )
    try:
        harness.start()
        harness.run(args.seconds)
        stats = harness.stats()
    finally:
        harness.stop()
    json.dump(stats, sys.stdout, indent=2, default=str)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
