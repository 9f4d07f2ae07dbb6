"""The least time the chip could take for the flash kernels' calls in the
trace (per call the larger of needed operations over peak bf16 FLOP/s and
needed bytes over peak bytes/s; causal half only, no recompute) over the
device time they took. Prints which roof binds."""

import json

from lib import cell


def read(run):
    helper = cell.load_module("metrics", "_pallas_attention")
    events = helper.kernel_events(run)
    if not events:
        return None
    least, roofs = helper.least_seconds(run, events)
    took = sum(e[-1] for e in events) / 1e9
    by_kernel = {}
    for e in events:
        by_kernel.setdefault(e[0], []).append(e[-1] / 1e6)
    print(json.dumps({"reader": "flash_roofline", "calls": len(events),
                      "binding_roof_by_call": roofs,
                      "mean_ms_by_kernel": {
                          k: sum(v) / len(v) for k, v in by_kernel.items()},
                      "least_s": least, "took_s": took}), flush=True)
    return 100.0 * least / took if took > 0 else None
